"""The token-policy cell ``trinity_ep16.ppo_update_8k`` of the chip benchmark, at a size a test run can
hold: the tiny ``rehearse_*`` sizes at ``32-true`` on the CPU, where the program and the plain reference
are the same mathematics (``test_seq_cell.py`` does this for the first language-model cell). Nothing here
is a device number."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, CHIP)

import common  # noqa: E402

CELL, CONFIG = "trinity_ep16.ppo_update_8k", "trinity_mini_ep16"
TEST_LIMIT = 1e-4  # float32 on both sides at tiny sizes: program and reference differ by rounding order alone
NEW_METRICS = ("window_attention_roofline_pct", "window_attention_device_ms", "shared_expert_device_ms")
SHARED_METRICS = (
    "lm_train_mfu_pct", "moe_device_ms", "mixer_device_ms", "head_loss_device_ms", "moe_experts_roofline_pct",
    "moe_load_max_over_mean", "rollout_feed_ms", "flash_attention_roofline_pct", "gmm_roofline_pct", "moe_compact_share",
)
ACCEPTED_CELLS = ("dv3_xl.chip_player", "lfm2_ep4.ppo_update_8k", CELL, "smallthinker_ep8.ppo_update_16k")  # a cell appended later may join a list


def _cell():
    cell = common.resolve_cell(CELL)
    limits = cell["config_file"]["limits"]
    cell["config_file"]["limits"] = {k: (0 if k == "tokens_wrong" else TEST_LIMIT) for k in limits}
    return cell


def _failed(verdict):
    return [k for k, v in verdict["compared"].items() if v["limit"] is not None and not v["value"] <= v["limit"]]


# ------------------------------------------------------------------ BENCHMARK.json and the files


def test_configuration_states_the_cut_and_the_catalog_numbers():
    config = common.load_json(CHIP, "configs", f"{CONFIG}.json")
    published = {  # the catalog entry's config (model-configs guide), every number and flag of it
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072, "model_type": "afmoe", "moe_intermediate_size": 1024,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048, "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_mm": True, "vocab_size": 200192,
    }
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: config["published"][k] for k in changed} == {k: published[k] for k in changed}
    assert (config["num_experts"], config["vocab_size"], config["num_hidden_layers"]) == (8, 25024, 5)
    assert config["vocab_size"] * 8 == 200192 and config["num_experts"] * 16 == config["router_outputs"] == 128
    assert len(config["layer_types"]) == 32 and config["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert config["layers_held"] == [0, 4, 5, 6, 7] and "Sixteen chips share each expert layer" in config["deployment"]
    kinds = [config["layer_types"][i] for i in config["layers_held"][1:]]
    assert kinds == ["sliding_attention"] * 3 + ["full_attention"]  # one whole period, 3 : 1 as the published 24 : 8
    for key in ("output_gate", "per_head_norms", "rotary_by_layer_type", "four_norms", "window_mask", "embedding_scale", "route_eps"):
        assert "modeling_afmoe.py" in config["assumed"][key], key
    for key in ("expert_bias", "critic", "ppo_recipe", "weights", "expert_placement"):
        assert key in config["assumed"]
    configuration_is_listed(ROOT)
    # the sizes the reference and the count run on are the file's own numbers
    sizes = config["sizes"]
    assert (sizes["experts_held"], sizes["num_experts"], sizes["vocab"], sizes["layers"]) == (8, 128, 25024, [0, 4, 5, 6, 7])
    assert (sizes["batch"], sizes["sequence"], sizes["prompt"]) == (2, 8192, 1024)
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "num_experts_per_tok", "num_shared_experts", "sliding_window", "num_dense_layers", "layer_types"):
        assert sizes[key] == config[key], key
    assert (sizes["routed_scaling_factor"], sizes["norm_topk_prob"], sizes["norm_eps"]) == (config["route_scale"], config["route_norm"], config["rms_norm_eps"])


def test_the_program_runs_the_configuration_file_s_model():
    from sheeprl_tpu.config import compose

    config = common.load_json(CHIP, "configs", f"{CONFIG}.json")
    traffic = common.load_json(CHIP, "traffic", "ppo_update_8k.json")
    cfg = compose(config_name="config", overrides=config["overrides"] + traffic["overrides"])
    lm, sizes = cfg.algo.lm, config["sizes"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts", "num_experts_per_tok", "experts_held", "num_dense_layers", "num_shared_experts", "sliding_window",
                "routed_scaling_factor", "route_eps", "norm_topk_prob", "rope_theta", "norm_eps"):
        assert lm[key] == sizes[key], key
    assert list(lm.layers) == sizes["layers"] and list(lm.layer_types) == sizes["layer_types"] and lm.vocab_held == sizes["vocab"]
    assert list(lm.rope_layer_types) == sizes["rope_layer_types"] == ["sliding_attention"]
    assert lm.attn_output_gate and lm.post_norms and lm.mup_enabled and not lm.tie_embedding
    assert (cfg.env.num_envs, cfg.algo.rollout_steps, cfg.algo.per_rank_sequence_length) == (sizes["batch"], sizes["sequence"], sizes["sequence"])
    assert (cfg.env.wrapper.prompt_tokens, cfg.env.wrapper.sampled_tokens) == (traffic["prompt_tokens"], traffic["sampled_tokens"]) == (1024, 7168)
    assert (cfg.algo.update_epochs, cfg.algo.per_rank_num_batches, cfg.algo.gamma) == (1, 1, 1.0)
    for key in ("clip_coef", "vf_coef", "ent_coef", "max_grad_norm", "gae_lambda"):
        assert float(cfg.algo[key]) == sizes[key]
    assert (float(cfg.algo.optimizer.lr), float(cfg.algo.optimizer.eps), float(cfg.algo.optimizer.weight_decay)) == (sizes["lr"], sizes["eps"], sizes["weight_decay"])
    assert cfg.fabric.precision == config["precision"] and cfg.fabric.player_on_host is False
    # and the program's leaves are the reference's, 504.1 M parameters of them
    from sheeprl_tpu.models import lm as program

    ref = common.load_module("reference", "trinity_ppo")
    spec = ref.param_spec(ref.sizes_from(sizes))
    assert {k: v[0] for k, v in program.param_shapes(program.LMConfig.from_cfg(lm)).items()} == {k: v[0] for k, v in spec.items()}
    assert sum(int(np.prod(shape)) for shape, _ in spec.values()) == 504_149_760


def test_flop_count_is_the_issue_s_arithmetic():
    config = common.load_json(CHIP, "configs", f"{CONFIG}.json")
    shared, sizes = common.load_module("", "flops"), config["sizes"]
    flops, count = shared.count_of(config)  # the configuration's count file, found by the name it gives
    assert count is flops.trinity_step_flops and shared.step_parts(config) == count(sizes)
    parts = {k: round(v / 1e12, 2) for k, v in count(sizes).items()}
    assert parts == {  # ISSUE 34's table, TFLOP a step
        "lm.embed": 0.0, "lm.swa": 16.49, "lm.attn": 5.98, "lm.dense_ffn": 3.71, "lm.moe.route": 0.10, "lm.moe.shared": 2.47,
        "lm.moe.experts": 1.24, "lm.head": 5.04, "total": 35.03,
    }
    tokens = 2 * 8192
    assert flops.pairs_inside(sizes, "attn") == 33_554_432 and flops.pairs_inside(sizes, "swa") == 14_680_064  # 43.75% of the triangle
    assert flops.pairs_inside({**sizes, "sequence": 2048}, "swa") == flops.pairs_inside({**sizes, "sequence": 2048}, "attn")
    assert flops.expected_pairs(sizes) == 4 * tokens / 2  # half a pair a token a layer, four expert layers: 1,024 rows an expert
    assert count(sizes, pairs_here=0.0)["lm.moe.experts"] == 0.0 and count(sizes, pairs_here=0.0)["lm.moe.shared"] == count(sizes)["lm.moe.shared"]
    assert shared.step_flops(config, pairs_here=4 * tokens) > shared.step_flops(config) == count(sizes)["total"]
    # what the readers ask the count file: its kernels by family, its scopes by layer
    full, band = shared.kernel_least(config, "attention"), shared.kernel_least(config, "window_attention")
    assert full["scope"] == "lm.attn" and full["flops"] == pytest.approx(3.30e12, rel=2e-3)
    assert band["scope"] == "lm.swa" and band["flops"] == pytest.approx(5.77e12, rel=2e-3) and band["flops"] == pytest.approx(4 * 0.4375 * full["flops"])
    assert full["bytes"] == 4 * 2 * (32 + 4) * 8192 * 128 * 2 and band["bytes"] == 4 * full["bytes"]  # keys and values at 4 heads
    gmm = shared.kernel_least(config, "gmm", 32768.0)
    assert gmm == {"scope": "lm.moe.experts", "flops": count(sizes, 32768.0)["lm.moe.experts"], "bytes": flops.gmm_bytes(sizes, 32768.0)}
    assert shared.layer_scopes(config, "expert layer") == ("lm.moe.route", "lm.moe.experts") and shared.layer_scopes(config, "token mixers") == ("lm.swa", "lm.attn")
    assert shared.layer_scopes(config, "head and loss") == ("lm.head", "ppo.loss")
    assert shared.layer_scopes(config, "window attention") == ("lm.swa",) and shared.layer_scopes(config, "shared expert") == ("lm.moe.shared",)
    # every scope the count looks for is one the program runs a part under
    from sheeprl_tpu.models import lm

    only_here = {lm.SCOPE_OF["swa"], "lm.moe.shared"}  # the parts the first family has not
    assert set(shared.scopes_of(config)) <= set(lm.SCOPES) | only_here | {"ppo.loss", "ppo.opt"}
    assert only_here <= set(shared.scopes_of(config)) and "lm.conv" not in shared.scopes_of(config)


# What ``<root>/BENCHMARK.json``'s lists say of this configuration and its cell, by membership and never by place:
# ``test_next_config.py`` asserts the order of entries once, and runs these on a copy with a further configuration appended.


def configuration_is_listed(root):
    bench = common.load_json(root, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    assert entry["reduced"] == common.load_json(CHIP, "configs", f"{CONFIG}.json")["reduced"]
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG and w["name"] in ACCEPTED_CELLS] == [CELL]


def cell_is_listed_with_its_metrics(root):
    cell = common.resolve_cell(CELL, root)
    reported = [m["name"] for m in cell["per_layer"]]
    assert set(NEW_METRICS) <= set(reported) and set(SHARED_METRICS) <= set(reported)
    assert [m["name"] for m in cell["end_to_end"]] == ["gsteps_per_s", "step_ms_p95", "setup_s"]
    other = [m["name"] for m in common.resolve_cell("lfm2_ep4.ppo_update_8k", root)["per_layer"]]
    assert not set(NEW_METRICS) & set(other) and set(SHARED_METRICS) <= set(other)
    by_name = {m["name"]: m for m in cell["per_layer"]}
    assert [by_name[n]["layer"] for n in NEW_METRICS] == ["token mixers", "token mixers", "expert layer"]
    assert cell["traffic_file"] == common.load_json(CHIP, "traffic", "ppo_update_8k.json") and cell["chips"] == 1 and len(cell["why"]) <= 200


LIST_CHECKS = (configuration_is_listed, cell_is_listed_with_its_metrics)


def test_the_cell_reports_the_shared_metrics_and_its_own_three():
    cell_is_listed_with_its_metrics(ROOT)


def test_reference_follows_a_given_routing_and_reports_its_own():
    import jax
    import jax.numpy as jnp

    ref = common.load_module("reference", "trinity_ppo")
    config = common.load_json(CHIP, "configs", f"{CONFIG}.json")
    s = ref.sizes_from({**config["sizes"], **config["rehearse_sizes"]})
    params = ref.make_params(ref.param_spec(s), 3)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, s["vocab"])
    with jax.default_matmul_precision("highest"):
        final, own = ref.forward(params, tokens, s)
        same, own_again = ref.forward(params, tokens, s, forced=own)
        other = (own + 1) % s["num_experts"]
        moved, still_own = ref.forward(params, tokens, s, forced=other)
    assert own.shape == (4, 64, 4)
    assert np.allclose(final, same, atol=1e-6) and np.array_equal(own, own_again)  # its own routing, given back, changes nothing
    assert float(jnp.max(jnp.abs(moved - final))) > 1e-3  # another routing is another result
    assert np.array_equal(still_own[0], own[0])  # the first expert layer sees the same state: its own choice stands
    # the groups are check_seq's as they are, and every leaf but the expert bias has one
    groups = {jax.tree_util.keystr(path): ref.group_of(jax.tree_util.keystr(path)) for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert set(groups.values()) == set(common.load_module("", "check_seq").GROUPS) | {None}
    assert groups["['head']"] == groups["['embed']"] == groups["['final_norm']"] == "embed"
    layer = "['layers']['layer_1']"
    assert groups[layer + "['attn']['gate']"] == groups[layer + "['op_post_norm']"] == groups[layer + "['op_norm']"] == "mixers"
    assert groups[layer + "['moe']['shared']['w2']"] == groups[layer + "['ffn_post_norm']"] == groups[layer + "['moe']['w1']"] == "experts"
    assert groups[layer + "['moe']['router']"] == "router" and groups[layer + "['moe']['bias']"] is None


# ------------------------------------------------------------------ the run itself, rehearsed


@pytest.mark.timeout(900)
def test_rehearsal_through_the_entry_point_exits_3_and_is_correct():
    """The command the driver runs, with ``--rehearse-cpu``: the whole ``--trace 0`` path at tiny sizes (set-up,
    three compared steps, the window, the reference, the comparison). Exit 3, never a pass; the result goes to
    standard error and every compared number is the rounding of float32."""
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload", CELL, "--seed", str(2**31 + 77), "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=800, cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "x"},
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""  # a rehearsal prints no result line
    line = next(l for l in proc.stderr.splitlines() if l.startswith("REHEARSAL"))
    result = json.loads(line.split(": ", 1)[1])
    assert result["correct"] and result["failed"] == 0 and result["steps"]["in_window"] >= 1
    assert set(result["metrics"]) == {"gsteps_per_s", "step_ms_p95", "setup_s"}
    limits = common.load_json(CHIP, "configs", f"{CONFIG}.json")["limits"]
    assert set(result["compared"]) == set(limits) and result["compared"]["tokens_wrong"]["value"] == 0
    assert all(v["value"] < TEST_LIMIT for v in result["compared"].values()), result["compared"]
    assert result["counters"]["Moe/pairs_total"] == 2 * 32 * 4 * 4  # tokens x experts a token x expert layers


def _run(monkeypatch, out_dir, fault=None, trace=False):
    """The driver's own ``run`` with the look for a chip skipped, ``fault`` planted under the timed path."""
    import jax

    driver = common.load_module("drivers", "seq_learner")
    faults = common.load_module("", "faults_seq")
    build = driver.build

    def broken(*a, **k):
        built = build(*a, **k)
        built["sound_train_fn"] = built["train_fn"]
        built["train_fn"] = faults.FAULTS[fault](built)
        return built

    with monkeypatch.context() as m:
        if fault is not None:
            m.setattr(driver, "build", broken)
        return driver.run(
            cell=_cell(), seed=2**31 + 12345, seconds=0.5, trace=trace, rehearse=True,
            devices=jax.devices()[:1], t_start=time.perf_counter(), out_dir=str(out_dir),
        )


@pytest.mark.timeout(900)
def test_three_steps_equal_the_reference_and_the_control_is_refused(monkeypatch, tmp_path):
    """Losses, the first gradient element by element, the parameters' change after three optimizer steps and the
    routing agree with the plain reference; the reference in bfloat16 (the nearest precision below the float32
    this test runs in), put in the program's place, does not."""
    check = common.load_module("", "check_seq")
    probes = []
    compare = check.Probe.compare
    monkeypatch.setattr(check.Probe, "compare", lambda self, config: probes.append(self) or compare(self, config))
    out = _run(monkeypatch, tmp_path)
    verdict = out["check"]
    assert verdict["correct"] and not verdict["missing"], verdict
    assert out["steps"]["in_window"] > 0 and out["failed"] == 0
    assert out["counters"]["Moe/pairs_total"] == 2 * 32 * 4 * 4  # tokens x experts a token x expert layers
    assert 0 < out["counters"]["Moe/pairs_here"] < out["counters"]["Moe/pairs_total"]
    assert out["compile"]["at_window_end"]["retraces"] == out["compile"]["at_window_start"]["retraces"]
    where = out["placement"]  # the experts placed on the four chips of the rehearsal by the pool's load: every expert once a layer
    assert np.array_equal(np.sort(where, -1), np.tile(np.arange(16), (4, 1))) and not np.array_equal(where, np.sort(where, -1))

    def fake_bf16(x):
        import jax
        import jax.numpy as jnp

        return x + jax.lax.stop_gradient(x.astype(jnp.bfloat16).astype(jnp.float32) - x)

    probe = probes[0]
    numbers = check.gaps(probe.reference_readings(quant=fake_bf16), probe.reference_readings())
    control = check.judge({"tokens_wrong": 0.0, **numbers}, _cell()["config_file"]["limits"])
    assert not control["correct"] and "grad_diff_leaf.experts" in _failed(control), control["compared"]


def _recorded(subject):
    """The chip's recorded readings of ``subject`` (calibration/<cell>.json), one set of numbers a seed."""
    record = common.load_json(CHIP, "calibration", f"{CELL}.json")
    take = (lambda v: v["sound"]["by_seed"]) if subject == "sound" else (lambda v: v[subject])
    by_number = {k: take(v) for k, v in record["numbers"].items()}
    return [{k: values[i] for k, values in by_number.items()} for i in range(len(by_number["tokens_wrong"]))]


def test_every_number_with_an_upper_reading_on_the_chip_is_compared():
    """The configuration's limits against the readings they were set from: every number is compared, and its limit lies
    over the sound program's largest reading with room and under the smallest reading of some control or fault."""
    record = common.load_json(CHIP, "calibration", f"{CELL}.json")
    limits = common.load_json(CHIP, "configs", f"{CONFIG}.json")["limits"]
    assert set(limits) == set(record["numbers"]) and all(v is not None for v in limits.values())
    for name, readings in record["numbers"].items():
        assert readings["limit"] == limits[name]
        if name == "tokens_wrong":
            continue
        uppers = [min(v) for k, v in readings.items() if k.startswith(("control_", "fault_"))]
        assert 1.5 * readings["sound"]["largest"] <= limits[name] < max(uppers), (name, readings["sound"]["largest"], uppers)


@pytest.mark.parametrize("subject", ["sound", "control_fp8", "fault_half_batch", "fault_state_unchanged", "fault_expert_left_out", "fault_three_experts"])
def test_the_recorded_chip_readings_judge_as_perf_md_says(subject):
    """Every recorded set of numbers through `check_seq.judge` with the configuration's limits: the sound program correct
    on every seed, the control and each fault on none."""
    check = common.load_module("", "check_seq")
    limits = common.load_json(CHIP, "configs", f"{CONFIG}.json")["limits"]
    verdicts = [check.judge(numbers, limits)["correct"] for numbers in _recorded(subject)]
    assert verdicts and all(v is (subject == "sound") for v in verdicts), (subject, verdicts)


@pytest.mark.timeout(1200)
@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged", "expert_left_out", "three_experts"])
def test_judge_refuses_each_fault_planted_under_the_timed_path(monkeypatch, tmp_path, fault):
    verdict = _run(monkeypatch, tmp_path, fault=fault)["check"]
    assert verdict["compared"]["tokens_wrong"]["value"] == 0  # the feed is sound: the fault is the program's
    assert not verdict["correct"], (fault, verdict["compared"])
    failed = _failed(verdict)
    assert "grad_diff.experts" in failed and "delta_gap.experts" in failed, (fault, failed)


@pytest.mark.timeout(900)
def test_traced_path_with_the_device_plane_stubbed_reads_the_three_new_metrics(monkeypatch, tmp_path):
    """``--trace 1`` on the CPU: the capture has no device plane, so ``reduce.reduce_dir`` is stubbed (the verify
    skill's recipe); the rest is the driver's own path. Every reader is called without raising, the device numbers
    read nothing, and with a reduction by this configuration's scopes and a peak the ten shared metrics and the
    three new ones read as numbers, each share under 100."""
    reduce = common.load_module("", "reduce")
    monkeypatch.setattr(reduce, "reduce_dir", lambda d: {"busy_s": 0.3, "window_s": 0.5, "n_devices": 1, "breakdown": {"device_ops": [], "idle_gaps": []}})
    run = _run(monkeypatch, tmp_path, trace=True)
    run.update(peak=None, cell=_cell())
    assert run["scopes"] is None and run["trace"]["breakdown"]["device_ms_a_step_by_scope"] == []

    def read():
        return {m["name"]: common.load_module("metrics", m["name"]).read(run) for m in run["cell"]["per_layer"]}

    values = read()
    assert values["rollout_feed_ms"] > 0 and values["moe_load_max_over_mean"] >= 1.0 and values["window_compiles"] == 0
    assert values["moe_compact_share"] == run["counters"]["Moe/compact_share"] and 0.0 <= values["moe_compact_share"] <= 1.0
    device = set(SHARED_METRICS) - {"rollout_feed_ms", "moe_load_max_over_mean", "moe_compact_share"} | set(NEW_METRICS)
    assert all(values[name] is None for name in device)  # device numbers: nothing on a CPU
    run["peak"] = common.peak_for("TPU v5 lite")
    run["config"] = common.load_json(CHIP, "configs", f"{CONFIG}.json")  # the published sizes, for the count
    run["counters"]["Moe/pairs_here"] = 32768.0
    run["scopes"] = {
        "steps": 10.0,
        "scopes": {"lm.swa": 2.4, "lm.attn": 0.9, "lm.dense_ffn": 0.3, "lm.moe.route": 0.2, "lm.moe.shared": 0.25, "lm.moe.experts": 0.5,
                   "lm.head": 0.3, "ppo.loss": 0.05, "ppo.opt": 0.1},
        "kernels": {"lm.swa": 1.2, "lm.attn": 0.5, "lm.moe.experts": 0.3},
    }
    run["steps"]["in_window"], run["window_s"] = 10, 6.0
    again = read()
    assert again["window_attention_device_ms"] == pytest.approx(240.0) and again["shared_expert_device_ms"] == pytest.approx(25.0)
    assert again["mixer_device_ms"] == pytest.approx(330.0) and again["window_attention_device_ms"] < again["mixer_device_ms"]
    assert again["moe_device_ms"] == pytest.approx(70.0) and again["head_loss_device_ms"] == pytest.approx(35.0)
    assert again["window_attention_roofline_pct"] == pytest.approx(100 * (5.772e12 / 197e12) / 0.12, rel=1e-3)
    assert again["flash_attention_roofline_pct"] == pytest.approx(100 * (3.299e12 / 197e12) / 0.05, rel=1e-3)
    assert again["gmm_roofline_pct"] == pytest.approx(100 * (1.237e12 / 197e12) / 0.03, rel=1e-3)
    assert again["moe_experts_roofline_pct"] == pytest.approx(again["gmm_roofline_pct"] * 0.6)
    assert again["lm_train_mfu_pct"] == pytest.approx(100 * 35.03e12 * 10 / 6.0 / 197e12, rel=1e-3)
    assert all(0 < again[name] < 100 for name in again if name.endswith("_pct") and again[name] is not None)
    assert all(again[name] is not None for name in SHARED_METRICS + NEW_METRICS)
    # on a program without the new scopes (the parent's, or the other configuration's) the new readers find nothing and do not raise
    run["config"] = common.load_json(CHIP, "configs", "lfm2_8b_a1b_ep4.json")
    assert all(common.load_module("metrics", name).read(run) is None for name in NEW_METRICS)
