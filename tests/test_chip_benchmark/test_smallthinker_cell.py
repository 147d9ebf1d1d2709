"""The token-policy cell ``smallthinker_ep8.ppo_update_16k`` of the chip benchmark, at a size a test run can
hold: the tiny ``rehearse_*`` sizes at ``32-true`` on the CPU, where the program and the plain reference
are the same mathematics (``test_trinity_cell.py`` does this for the second language-model cell). Nothing here
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

CELL, CONFIG, TRAFFIC = "smallthinker_ep8.ppo_update_16k", "smallthinker_21b_ep8", "ppo_update_16k"
TEST_LIMIT = 1e-4  # float32 on both sides at tiny sizes: program and reference differ by rounding order alone
NEW_METRICS = ("moe_route_device_ms",)
SHARED_METRICS = (
    "lm_train_mfu_pct", "moe_device_ms", "mixer_device_ms", "head_loss_device_ms", "moe_experts_roofline_pct",
    "moe_load_max_over_mean", "rollout_feed_ms", "flash_attention_roofline_pct", "gmm_roofline_pct", "moe_compact_share",
    "window_attention_roofline_pct", "window_attention_device_ms",
)
# `half_batch` halves the sequences' axis: at one sequence a step it has nothing to halve (PERF.md, PR 36)
FAULTS = ("state_unchanged", "expert_left_out", "three_experts")
ACCEPTED_CELLS = ("dv3_xl.chip_player", "lfm2_ep4.ppo_update_8k", "trinity_ep16.ppo_update_8k", CELL)  # a cell appended later may join a list


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
    layout = [0, 1, 1, 1] * 13
    published = {  # the catalog entry's config (model-configs guide), every key of it
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384, "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": layout, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936,
    }
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers", "moe_num_primary_experts", "vocab_size"}
    assert {k: config["published"][k] for k in changed} == {k: published[k] for k in changed}
    assert (config["moe_num_primary_experts"], config["vocab_size"], config["num_hidden_layers"]) == (8, 18992, 4)
    assert config["vocab_size"] * 8 == 151936 and config["moe_num_primary_experts"] * 8 == config["router_outputs"] == 64
    assert config["layers_held"] == [0, 1, 2, 3] and "Eight chips share each layer" in config["deployment"]
    assert [config["sliding_window_layout"][i] for i in config["layers_held"]] == [0, 1, 1, 1]  # one whole period: full, then three sliding
    for key in ("sources", "router_input", "router_scoring", "no_router_bias", "no_per_head_norm", "rotary_by_rope_layout", "window_mask",
                "expert_activation", "primary_experts_only", "no_attention_bias", "router_precision", "critic", "ppo_recipe", "weights",
                "expert_placement"):
        assert key in config["assumed"], key
    assert "llm_build_smallthinker" in config["assumed"]["sources"] and "modeling_smallthinker.py" in config["assumed"]["sources"]
    configuration_is_listed(ROOT)
    # the sizes the reference and the count run on are the file's own numbers
    sizes = config["sizes"]
    assert (sizes["experts_held"], sizes["num_experts"], sizes["vocab"], sizes["layers"]) == (8, 64, 18992, [0, 1, 2, 3])
    assert (sizes["batch"], sizes["sequence"], sizes["prompt"]) == (1, 16384, 1024)
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "norm_topk_prob"):
        assert sizes[key] == config[key], key
    assert (sizes["moe_intermediate_size"], sizes["num_experts_per_tok"], sizes["sliding_window"], sizes["norm_eps"], sizes["rope_theta"]) == (
        config["moe_ffn_hidden_size"], config["moe_num_active_primary_experts"], config["sliding_window_size"], config["rms_norm_eps"], config["rope_theta"])
    assert sizes["layer_types"] == ["sliding_attention" if on else "full_attention" for on in config["sliding_window_layout"]]
    assert sizes["rope_layer_types"] == ["sliding_attention"] and config["rope_layout"] == config["sliding_window_layout"]


def test_the_program_runs_the_configuration_file_s_model():
    from sheeprl_tpu.config import compose

    config = common.load_json(CHIP, "configs", f"{CONFIG}.json")
    traffic = common.load_json(CHIP, "traffic", f"{TRAFFIC}.json")
    cfg = compose(config_name="config", overrides=config["overrides"] + traffic["overrides"])
    lm, sizes = cfg.algo.lm, config["sizes"]
    for key in ("hidden_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts",
                "num_experts_per_tok", "experts_held", "num_dense_layers", "sliding_window", "norm_topk_prob", "rope_theta", "norm_eps"):
        assert lm[key] == sizes[key], key
    assert list(lm.layers) == sizes["layers"] and list(lm.layer_types) == sizes["layer_types"] and lm.vocab_held == sizes["vocab"]
    assert list(lm.rope_layer_types) == sizes["rope_layer_types"] == ["sliding_attention"]
    assert lm.early_router and lm.router_apply_softmax and lm.hidden_act == "relu" and not lm.qk_norm and not lm.tie_embedding
    assert lm.routed_scaling_factor == 1.0 and not lm.get("attn_output_gate", False) and not lm.get("post_norms", False)
    assert (cfg.env.num_envs, cfg.algo.rollout_steps, cfg.algo.per_rank_sequence_length) == (sizes["batch"], sizes["sequence"], sizes["sequence"])
    assert (cfg.env.num_envs, cfg.env.wrapper.prompt_tokens, cfg.env.wrapper.sampled_tokens) == (
        traffic["sequences"], traffic["prompt_tokens"], traffic["sampled_tokens"]) == (1, 1024, 15360)
    assert (cfg.algo.update_epochs, cfg.algo.per_rank_num_batches, cfg.algo.gamma) == (1, 1, 1.0)
    for key in ("clip_coef", "vf_coef", "ent_coef", "max_grad_norm", "gae_lambda"):
        assert float(cfg.algo[key]) == sizes[key]
    assert (float(cfg.algo.optimizer.lr), float(cfg.algo.optimizer.eps), float(cfg.algo.optimizer.weight_decay)) == (sizes["lr"], sizes["eps"], sizes["weight_decay"])
    assert cfg.fabric.precision == config["precision"] and cfg.fabric.player_on_host is False
    # the traffic file has the accepted one's keys and driver
    accepted = common.load_json(CHIP, "traffic", "ppo_update_8k.json")
    assert set(traffic) == set(accepted) and traffic["driver"] == accepted["driver"] == "seq_learner"
    assert {k: traffic[k] for k in ("zipf_a", "pool_rollouts", "rewards", "warmup_steps", "trace_seconds", "overrides")} == {
        k: accepted[k] for k in ("zipf_a", "pool_rollouts", "rewards", "warmup_steps", "trace_seconds", "overrides")}
    # and the program's leaves are the reference's, 370.5 M parameters of them (ISSUE 36's arithmetic)
    from sheeprl_tpu.models import lm as program

    ref = common.load_module("reference", "smallthinker_ppo")
    spec = ref.param_spec(ref.sizes_from(sizes))
    assert {k: v[0] for k, v in program.param_shapes(program.LMConfig.from_cfg(lm)).items()} == {k: v[0] for k, v in spec.items()}
    assert sum(int(np.prod(shape)) for shape, _ in spec.values()) == 4 * 68_326_400 + 2 * 48_619_520 + 5_120 == 370_549_760
    assert not any(path[-1] in ("q_norm", "k_norm", "bias") for path in spec)


def test_flop_count_is_the_issue_s_arithmetic():
    config = common.load_json(CHIP, "configs", f"{CONFIG}.json")
    shared, sizes = common.load_module("", "flops"), config["sizes"]
    flops, count = shared.count_of(config)  # the configuration's count file, found by the name it gives
    assert count is flops.smallthinker_step_flops and shared.step_parts(config) == count(sizes)
    parts = {k: round(v / 1e12, 2) for k, v in count(sizes).items()}
    # ISSUE 36, TFLOP a step: projections 8.25 (2.06 a layer) and attention 13.35 (full 5.77 + 3 x 2.53) by the scope they run under
    assert parts == {"lm.embed": 0.0, "lm.swa": 13.76, "lm.attn": 7.83, "lm.moe.route": 0.06, "lm.moe.experts": 1.74, "lm.head": 4.78, "total": 28.18}
    tokens = 16384
    projections = 6.0 * tokens * (2 * 2560 * 3584 + 2 * 2560 * 512)
    assert round(4 * projections / 1e12, 2) == 8.25
    assert count(sizes)["lm.attn"] + count(sizes)["lm.swa"] - 4 * projections == pytest.approx(13.35e12, rel=1e-3)
    assert flops.pairs_inside(sizes, "attn") == 134_217_728 and flops.pairs_inside(sizes, "swa") == 58_720_256  # 43.75% of the triangle
    assert flops.pairs_inside({**sizes, "sequence": 4096}, "swa") == flops.pairs_inside({**sizes, "sequence": 4096}, "attn")
    assert flops.expected_pairs(sizes) == 4 * 12288  # 12,288 pairs a layer: 1,536 rows a held expert
    assert count(sizes, pairs_here=0.0)["lm.moe.experts"] == 0.0
    assert shared.step_flops(config, pairs_here=4 * tokens) > shared.step_flops(config) == count(sizes)["total"]
    # what the readers ask the count file: its kernels by family, its scopes by layer
    full, band = shared.kernel_least(config, "attention"), shared.kernel_least(config, "window_attention")
    assert full["scope"] == "lm.attn" and full["flops"] == pytest.approx(5.77e12, rel=2e-3)
    assert band["scope"] == "lm.swa" and band["flops"] == pytest.approx(3 * 2.525e12, rel=2e-3) and band["flops"] == pytest.approx(3 * 0.4375 * full["flops"])
    assert full["bytes"] == 4 * 1 * (28 + 4) * 16384 * 128 * 2 and band["bytes"] == 3 * full["bytes"]  # keys and values at 4 heads
    gmm = shared.kernel_least(config, "gmm", 40000.0)
    assert gmm == {"scope": "lm.moe.experts", "flops": count(sizes, 40000.0)["lm.moe.experts"], "bytes": flops.gmm_bytes(sizes, 40000.0)}
    assert shared.layer_scopes(config, "expert layer") == ("lm.moe.route", "lm.moe.experts") and shared.layer_scopes(config, "token mixers") == ("lm.swa", "lm.attn")
    assert shared.layer_scopes(config, "head and loss") == ("lm.head", "ppo.loss")
    assert shared.layer_scopes(config, "window attention") == ("lm.swa",) and shared.layer_scopes(config, "routing") == ("lm.moe.route",)
    assert shared.layer_scopes(config, "shared expert") == ()  # this model has none: that reader finds nothing here
    # every scope the count looks for is one the program runs a part under
    from sheeprl_tpu.models import lm

    assert set(shared.scopes_of(config)) <= set(lm.SCOPES) | {lm.SCOPE_OF["swa"]} | {"ppo.loss", "ppo.opt"}
    assert lm.SCOPE_OF["swa"] in shared.scopes_of(config) and not {"lm.conv", "lm.dense_ffn", "lm.moe.shared"} & set(shared.scopes_of(config))


# What ``<root>/BENCHMARK.json``'s lists say of this configuration and its cell, by membership and never by place:
# ``test_next_config.py`` asserts the order of entries once, and runs these on a copy with a further configuration appended.


def configuration_is_listed(root):
    bench = common.load_json(root, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json"
    assert entry["reduced"] == common.load_json(CHIP, "configs", f"{CONFIG}.json")["reduced"] and entry["file"] == f"benchmarks/chip/configs/{CONFIG}.json"
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG and w["name"] in ACCEPTED_CELLS] == [CELL]


def cell_is_listed_with_its_metrics(root):
    cell = common.resolve_cell(CELL, root)
    reported = [m["name"] for m in cell["per_layer"]]
    assert set(NEW_METRICS) <= set(reported) and set(SHARED_METRICS) <= set(reported) and "shared_expert_device_ms" not in reported
    assert [m["name"] for m in cell["end_to_end"]] == ["gsteps_per_s", "step_ms_p95", "setup_s"]
    for other in ("lfm2_ep4.ppo_update_8k", "trinity_ep16.ppo_update_8k"):
        assert not set(NEW_METRICS) & {m["name"] for m in common.resolve_cell(other, root)["per_layer"]}
    by_name = {m["name"]: m for m in cell["per_layer"]}
    entry = dict(by_name["moe_route_device_ms"])
    entry["workloads"] = [w for w in entry["workloads"] if w in ACCEPTED_CELLS]
    assert entry == {
        "name": "moe_route_device_ms", "unit": "ms", "better": "lower", "source": "device_trace", "layer": "expert layer", "moves": "gsteps_per_s",
        "workloads": [CELL],
    }
    assert cell["traffic_file"] == common.load_json(CHIP, "traffic", f"{TRAFFIC}.json") and cell["chips"] == 1 and len(cell["why"]) <= 200


LIST_CHECKS = (configuration_is_listed, cell_is_listed_with_its_metrics)


def test_the_cell_reports_the_shared_metrics_and_its_own():
    cell_is_listed_with_its_metrics(ROOT)


def test_reference_follows_a_given_routing_and_reports_its_own():
    import jax
    import jax.numpy as jnp

    ref = common.load_module("reference", "smallthinker_ppo")
    config = common.load_json(CHIP, "configs", f"{CONFIG}.json")
    s = ref.sizes_from({**config["sizes"], **config["rehearse_sizes"]})
    params = ref.make_params(ref.param_spec(s), 3)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 32), 0, s["vocab"])
    with jax.default_matmul_precision("highest"):
        final, own = ref.forward(params, tokens, s)
        same, own_again = ref.forward(params, tokens, s, forced=own)
        other = (own + 1) % s["num_experts"]
        moved, still_own = ref.forward(params, tokens, s, forced=other)
    assert own.shape == (4, 32, 3)
    assert np.allclose(final, same, atol=1e-6) and np.array_equal(own, own_again)  # its own routing, given back, changes nothing
    assert float(jnp.max(jnp.abs(moved - final))) > 1e-3  # another routing is another result
    assert np.array_equal(still_own[0], own[0])  # the first layer's router sees the same input: its own choice stands
    # the seeded weights' scales (`assumed.weights`): the embedding's elements of variance 1, the projections into the residual stream scaled down
    wide = ref.make_params(ref.param_spec(ref.sizes_from(config["sizes"] | {"vocab": 512, "experts_held": 1, "layers": [0]})), 5)
    layer = wide["layers"]["layer_0"]
    assert float(jnp.std(wide["embed"])) == pytest.approx(1.0, rel=0.02) and float(jnp.std(wide["head"])) == pytest.approx(2560**-0.5, rel=0.02)
    assert float(jnp.std(layer["attn"]["o"])) == pytest.approx((3584 * 104) ** -0.5, rel=0.02)
    assert float(jnp.std(layer["moe"]["w2"])) == pytest.approx((768 * 104) ** -0.5, rel=0.02)
    assert float(jnp.std(layer["attn"]["q"])) == pytest.approx(2560**-0.5, rel=0.02) == pytest.approx(float(jnp.std(layer["moe"]["router"])), rel=0.05)
    # the groups are check_seq's as they are, and every leaf has one
    groups = {jax.tree_util.keystr(path): ref.group_of(jax.tree_util.keystr(path)) for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert set(groups.values()) == set(common.load_module("", "check_seq").GROUPS)
    assert groups["['head']"] == groups["['embed']"] == groups["['final_norm']"] == "embed"
    layer = "['layers']['layer_1']"
    assert groups[layer + "['attn']['o']"] == groups[layer + "['op_norm']"] == "mixers"
    assert groups[layer + "['ffn_norm']"] == groups[layer + "['moe']['w1']"] == "experts" and groups[layer + "['moe']['router']"] == "router"
    # placing the experts permutes the router's outputs, and nothing else
    where = np.tile(np.arange(16)[::-1], (4, 1))
    placed = ref.place_experts(params, where, s)
    assert np.array_equal(placed["layers"]["layer_2"]["moe"]["router"], params["layers"]["layer_2"]["moe"]["router"][:, ::-1])
    assert placed["layers"]["layer_2"]["moe"]["w1"] is params["layers"]["layer_2"]["moe"]["w1"]


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
    assert result["counters"]["Moe/pairs_total"] == 1 * 32 * 3 * 4  # tokens x experts a token x expert layers
    assert result["counters"]["Moe/compact_share"] == 1.0


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
    assert out["counters"]["Moe/pairs_total"] == 1 * 32 * 3 * 4  # tokens x experts a token x expert layers
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


def test_every_limit_lies_between_its_two_readings_on_the_chip():
    """The configuration's limits against the readings they were set from (PERF.md section 5). A number that some control
    or fault reads above the sound program is compared, with a limit at least 1.5 times over the sound program's largest
    reading and under the smallest reading of that control or fault; three later-step losses whose upper reading lies
    under twice the sound program's largest are shown and not compared (a limit there would refuse a sound seed or pass the fault); a number
    that nothing reads above the sound program (the first step's losses: neither the precision nor a fault moves them)
    carries an accepted cell's limit, with three times of room."""
    record = common.load_json(CHIP, "calibration", f"{CELL}.json")
    limits = common.load_json(CHIP, "configs", f"{CONFIG}.json")["limits"]
    accepted = [common.load_json(CHIP, "configs", f"{name}.json")["limits"] for name in ("lfm2_8b_a1b_ep4", "trinity_mini_ep16")]
    assert set(limits) == set(record["numbers"]) and set(limits) == set(accepted[1])
    assert {k for k, v in limits.items() if v is None} == {"policy.step3", "value.step2", "value.step3"}
    for name, readings in record["numbers"].items():
        assert readings["limit"] == limits[name]
        if name == "tokens_wrong":
            continue
        largest = readings["sound"]["largest"]
        upper = max(min(v) for k, v in readings.items() if k.startswith(("control_", "fault_")))
        if limits[name] is None:  # no room: the upper reading lies under twice the sound program's largest (or under it)
            assert upper < 2 * largest, (name, largest, upper)
        elif upper <= largest:
            assert limits[name] in [a[name] for a in accepted] and limits[name] >= 3 * largest, (name, largest, upper)
        else:
            assert 1.5 * largest <= limits[name] < upper, (name, largest, upper)


@pytest.mark.parametrize("subject", ["sound", "control_fp8"] + [f"fault_{name}" for name in FAULTS])
def test_the_recorded_chip_readings_judge_as_perf_md_says(subject):
    """Every recorded set of numbers through `check_seq.judge` with the configuration's limits: the sound program correct
    on every seed, the control and each fault on none."""
    check = common.load_module("", "check_seq")
    limits = common.load_json(CHIP, "configs", f"{CONFIG}.json")["limits"]
    verdicts = [check.judge(numbers, limits)["correct"] for numbers in _recorded(subject)]
    assert verdicts and all(v is (subject == "sound") for v in verdicts), (subject, verdicts)


@pytest.mark.timeout(1200)
@pytest.mark.parametrize("fault", FAULTS)
def test_judge_refuses_each_fault_planted_under_the_timed_path(monkeypatch, tmp_path, fault):
    verdict = _run(monkeypatch, tmp_path, fault=fault)["check"]
    assert verdict["compared"]["tokens_wrong"]["value"] == 0  # the feed is sound: the fault is the program's
    assert not verdict["correct"], (fault, verdict["compared"])
    failed = _failed(verdict)
    assert "grad_diff.experts" in failed and "delta_gap.experts" in failed, (fault, failed)


@pytest.mark.timeout(900)
def test_traced_path_with_the_device_plane_stubbed_reads_the_shared_metrics_and_the_routing_s(monkeypatch, tmp_path):
    """``--trace 1`` on the CPU: the capture has no device plane, so ``reduce.reduce_dir`` is stubbed (the verify
    skill's recipe); the rest is the driver's own path. Every reader is called without raising, the device numbers
    read nothing, and with a reduction by this configuration's scopes and a peak the twelve shared metrics and
    ``moe_route_device_ms`` read as numbers, each share under 100."""
    reduce = common.load_module("", "reduce")
    monkeypatch.setattr(reduce, "reduce_dir", lambda d: {"busy_s": 0.3, "window_s": 0.5, "n_devices": 1, "breakdown": {"device_ops": [], "idle_gaps": []}})
    run = _run(monkeypatch, tmp_path, trace=True)
    run.update(peak=None, cell=_cell())
    assert run["scopes"] is None and run["trace"]["breakdown"]["device_ms_a_step_by_scope"] == []

    def read():
        return {m["name"]: common.load_module("metrics", m["name"]).read(run) for m in run["cell"]["per_layer"]}

    values = read()
    assert values["rollout_feed_ms"] > 0 and values["moe_load_max_over_mean"] >= 1.0 and values["window_compiles"] == 0
    assert values["moe_compact_share"] == run["counters"]["Moe/compact_share"] == 1.0
    device = set(SHARED_METRICS) - {"rollout_feed_ms", "moe_load_max_over_mean", "moe_compact_share"} | set(NEW_METRICS)
    assert all(values[name] is None for name in device)  # device numbers: nothing on a CPU
    run["peak"] = common.peak_for("TPU v5 lite")
    run["config"] = common.load_json(CHIP, "configs", f"{CONFIG}.json")  # the published sizes, for the count
    run["counters"]["Moe/pairs_here"] = 49152.0
    run["scopes"] = {
        "steps": 10.0,
        "scopes": {"lm.swa": 1.5, "lm.attn": 0.8, "lm.moe.route": 0.13, "lm.moe.experts": 0.5, "lm.head": 0.6, "ppo.loss": 0.08, "ppo.opt": 0.13},
        "kernels": {"lm.swa": 0.9, "lm.attn": 0.6, "lm.moe.experts": 0.3},
    }
    run["steps"]["in_window"], run["window_s"] = 10, 4.0
    again = read()
    assert again["moe_route_device_ms"] == pytest.approx(13.0) and again["moe_device_ms"] == pytest.approx(63.0)
    assert again["window_attention_device_ms"] == pytest.approx(150.0) and again["mixer_device_ms"] == pytest.approx(230.0)
    assert again["head_loss_device_ms"] == pytest.approx(68.0)
    assert again["window_attention_roofline_pct"] == pytest.approx(100 * (7.576e12 / 197e12) / 0.09, rel=1e-3)
    assert again["flash_attention_roofline_pct"] == pytest.approx(100 * (5.772e12 / 197e12) / 0.06, rel=1e-3)
    assert again["gmm_roofline_pct"] == pytest.approx(100 * (1.7395e12 / 197e12) / 0.03, rel=1e-3)
    assert again["moe_experts_roofline_pct"] == pytest.approx(again["gmm_roofline_pct"] * 0.6)
    assert again["lm_train_mfu_pct"] == pytest.approx(100 * 28.177e12 * 10 / 4.0 / 197e12, rel=1e-3)
    assert all(0 < again[name] < 100 for name in again if name.endswith("_pct") and again[name] is not None)
    assert all(again[name] is not None for name in SHARED_METRICS + NEW_METRICS)
    # on a configuration whose count file lists no layer ``routing`` (the accepted ones, on either side of this PR) the new reader finds nothing and does not raise
    for other in ("lfm2_8b_a1b_ep4", "trinity_mini_ep16"):
        run["config"] = common.load_json(CHIP, "configs", f"{other}.json")
        assert all(common.load_module("metrics", name).read(run) is None for name in NEW_METRICS)
