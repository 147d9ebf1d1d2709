"""The token-policy cell ``lfm2_ep4.ppo_update_8k`` of the chip benchmark, at a size a test run
can hold: the tiny ``rehearse_*`` sizes at ``32-true`` on the CPU, where the program and the plain
reference are the same mathematics. Nothing here is a device number."""

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

CELL = "lfm2_ep4.ppo_update_8k"
TEST_LIMIT = 1e-4  # float32 on both sides at tiny sizes: program and reference differ by rounding order alone


def _cell():
    cell = common.resolve_cell(CELL)
    limits = cell["config_file"]["limits"]
    cell["config_file"]["limits"] = {k: (0 if k == "tokens_wrong" else TEST_LIMIT) for k in limits}
    return cell


def _failed(verdict):
    return [k for k, v in verdict["compared"].items() if v["limit"] is not None and not v["value"] <= v["limit"]]


# ------------------------------------------------------------------ BENCHMARK.json and the files


def test_configuration_states_the_cut_and_the_catalog_numbers():
    config = common.load_json(CHIP, "configs", "lfm2_8b_a1b_ep4.json")
    published = {  # the catalog entry's config (model-configs guide), every number of it
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536,
    }
    changed = {k for k, v in published.items() if config[k] != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert {k: config["published"][k] for k in changed} == {k: published[k] for k in changed}
    assert (config["num_experts"], config["vocab_size"], config["num_hidden_layers"]) == (8, 16384, 5)
    assert len(config["layer_types"]) == 24 and config["layer_types"].count("full_attention") == 6
    assert config["layers_held"] == [0, 2, 3, 4, 5] and "Four chips share each layer" in config["deployment"]
    kinds = [config["layer_types"][i] for i in config["layers_held"][1:]]
    assert sorted(kinds) == ["conv", "conv", "conv", "full_attention"]  # one whole period, 3 : 1 as the published 18 : 6
    for key in ("head_dim", "tie_embedding", "router_precision", "expert_bias", "intermediate_size", "critic", "ppo_recipe"):
        assert key in config["assumed"]
    # the sizes the reference and the count run on are the file's own numbers
    sizes = config["sizes"]
    assert (sizes["experts_held"], sizes["num_experts"], sizes["vocab"], sizes["layers"]) == (8, 32, 16384, [0, 2, 3, 4, 5])
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads"):
        assert sizes[key] == config[key]


def test_the_program_runs_the_configuration_file_s_model():
    from sheeprl_tpu.config import compose

    config = common.load_json(CHIP, "configs", "lfm2_8b_a1b_ep4.json")
    traffic = common.load_json(CHIP, "traffic", "ppo_update_8k.json")
    cfg = compose(config_name="config", overrides=config["overrides"] + traffic["overrides"])
    lm, sizes = cfg.algo.lm, config["sizes"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "num_experts", "num_experts_per_tok", "experts_held", "num_dense_layers", "conv_L_cache"):
        assert lm[key] == sizes[key], key
    assert list(lm.layers) == sizes["layers"] and list(lm.layer_types) == sizes["layer_types"] and lm.vocab_held == sizes["vocab"]
    assert (cfg.env.num_envs, cfg.algo.rollout_steps, cfg.algo.per_rank_sequence_length) == (sizes["batch"], sizes["sequence"], sizes["sequence"])
    assert (cfg.env.wrapper.prompt_tokens, cfg.env.wrapper.sampled_tokens) == (traffic["prompt_tokens"], traffic["sampled_tokens"]) == (1024, 7168)
    assert (cfg.algo.update_epochs, cfg.algo.per_rank_num_batches, cfg.algo.gamma) == (1, 1, 1.0)
    for key, name in (("clip_coef", "clip_coef"), ("vf_coef", "vf_coef"), ("ent_coef", "ent_coef"), ("max_grad_norm", "max_grad_norm"), ("gae_lambda", "gae_lambda")):
        assert float(cfg.algo[name]) == sizes[key]
    assert (float(cfg.algo.optimizer.lr), float(cfg.algo.optimizer.eps), float(cfg.algo.optimizer.weight_decay)) == (sizes["lr"], sizes["eps"], sizes["weight_decay"])
    assert cfg.fabric.precision == config["precision"] and cfg.fabric.player_on_host is False


def test_flop_count_is_the_issue_s_arithmetic():
    config = common.load_json(CHIP, "configs", "lfm2_8b_a1b_ep4.json")
    shared, sizes = common.load_module("", "flops"), config["sizes"]
    flops, count = shared.count_of(config)  # the configuration's count file, found by the name it gives
    assert count is flops.lfm2_step_flops and shared.step_parts(config) == count(sizes)
    parts = flops.lfm2_step_flops(sizes)
    tokens = 2 * 8192
    assert parts["total"] == pytest.approx(21.26e12, rel=2e-3) and parts["total"] / tokens == pytest.approx(1.30e9, rel=5e-3)
    macs = {k: v / 6 / tokens for k, v in parts.items()}  # multiply-adds a token forwards
    assert macs["lm.conv"] + macs["lm.attn"] == pytest.approx(77.6e6 + 16.8e6, rel=2e-3)
    assert macs["lm.dense_ffn"] == pytest.approx(44.0e6, rel=2e-3) and macs["lm.moe.experts"] == pytest.approx(4 * 11.0e6, rel=2e-3)
    assert macs["lm.head"] == pytest.approx(33.6e6, rel=2e-3)
    assert flops.expected_pairs(sizes) == 4 * tokens  # one pair a token a layer, four expert layers
    assert flops.lfm2_step_flops(sizes, pairs_here=0.0)["lm.moe.experts"] == 0.0
    assert shared.step_flops(config, pairs_here=2 * 4 * tokens) > shared.step_flops(config) == parts["total"]
    assert shared.step_flops({"flops": "flops_lfm2:lfm2_step_flops", "sizes": sizes}) == parts["total"]  # the form a new configuration gives
    with pytest.raises(KeyError):
        shared.step_flops({"name": "x", "flops": "flops_lfm2:no_such_count", "sizes": sizes})
    with pytest.raises(FileNotFoundError):
        shared.step_flops({"name": "x", "flops": "nowhere_step_flops", "sizes": sizes})
    least = flops.flash_attention_least(sizes)
    assert least["flops"] == pytest.approx(1.649e12, rel=1e-3) and least["bytes"] == pytest.approx(0.537e9, rel=1e-2)
    # what the shared readers ask the count file: its kernels by family, its scopes by layer
    assert shared.kernel_least(config, "attention") == {"scope": "lm.attn", **least}
    gmm = shared.kernel_least(config, "gmm", 65536.0)
    assert gmm == {"scope": "lm.moe.experts", "flops": flops.lfm2_step_flops(sizes, 65536.0)["lm.moe.experts"], "bytes": flops.lfm2_gmm_bytes(sizes, 65536.0)}
    assert shared.kernel_least(config, "no such family") is None and shared.kernel_least(common.load_json(CHIP, "configs", "dv3_xl_crafter.json"), "gmm") is None
    assert shared.layer_scopes(config, "expert layer") == ("lm.moe.route", "lm.moe.experts") and shared.layer_scopes(config, "device") == ()


def test_rollouts_come_from_the_seed_and_are_zipf_with_one_terminal_reward():
    rollouts = common.load_module("", "rollouts")
    sizes = {"sequence": 64, "batch": 2, "vocab": 512, "prompt": 16}
    traffic = common.load_json(CHIP, "traffic", "ppo_update_8k.json")
    a, b = rollouts.make_pool(2**31 + 5, sizes, traffic), rollouts.make_pool(2**31 + 5, sizes, traffic)
    assert len(a) == traffic["pool_rollouts"] == 8
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    r = a[0]
    assert r["tokens"].shape == (64, 2, 1) and r["tokens"].max() < 512
    assert np.array_equal(r["actions"][:-1], r["tokens"][1:])  # a step's action is the next step's token
    assert np.array_equal(r["sampled"][:, 0, 0], [0.0] * 16 + [1.0] * 48)
    assert not r["rewards"][:-1].any() and set(np.unique(r["rewards"][-1])) <= {0.0, 1.0} and r["dones"][-1].all()
    ids = np.concatenate([x["tokens"].reshape(-1) for x in a])
    assert np.mean(ids == 0) > 5 * np.mean(ids == 9)  # Zipf: the first id far ahead of the tenth


def test_reference_follows_a_given_routing_and_reports_its_own():
    import jax
    import jax.numpy as jnp

    ref = common.load_module("reference", "lfm2_ppo")
    check = common.load_module("", "check_seq")
    sizes = {**common.load_json(CHIP, "configs", "lfm2_8b_a1b_ep4.json")["sizes"], **common.load_json(CHIP, "configs", "lfm2_8b_a1b_ep4.json")["rehearse_sizes"]}
    s = ref.sizes_from(sizes)
    params = ref.make_params(ref.param_spec(s), 3)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, s["vocab"])
    with jax.default_matmul_precision("highest"):
        final, own = ref.forward(params, tokens, s)
        same, own_again = ref.forward(params, tokens, s, forced=own)
        other = (own + 1) % s["num_experts"]
        moved, still_own = ref.forward(params, tokens, s, forced=other)
    assert np.allclose(final, same, atol=1e-6) and np.array_equal(own, own_again)  # its own routing, given back, changes nothing
    assert float(jnp.max(jnp.abs(moved - final))) > 1e-3  # another routing is another result
    assert np.array_equal(still_own[0], own[0])  # the first expert layer sees the same state: its own choice stands
    assert check.route_disagree(np.asarray(own), np.asarray(own)) == 0.0
    assert check.route_disagree(np.asarray(own)[..., :1], np.asarray(own)) == 0.5  # one expert a token where the reference takes two
    # the train call reports its routing in the order it took the sequences in; the comparison puts it back
    key = jax.random.PRNGKey(4)
    order = np.asarray(jax.random.permutation(jax.random.split(key, 1)[0], 2))
    by_seq = np.asarray(own).reshape(own.shape[0], 2, 16, -1)
    assert np.array_equal(check.in_feed_order(by_seq[:, order].reshape(own.shape), key, 2), np.asarray(own))


def test_balanced_groups_spread_the_load_evenly_and_renumber_every_expert_once():
    """Experts placed on chips by load: every expert once, in equal groups, by number within a group, and the
    groups' loads within a hundredth of each other at loads as uneven as Zipf ids make them (the fullest
    expert at twice the mean and more), where a chip's share as drawn is 3% off and more."""
    driver = common.load_module("drivers", "seq_learner")
    rng = np.random.default_rng(5)
    for _ in range(20):
        load = np.round(2048 * rng.lognormal(0.0, 0.35, size=32))
        order = driver.balanced_groups(load, 4)
        assert sorted(order.tolist()) == list(range(32)) and order.dtype == np.int32
        groups = order.reshape(4, 8)
        assert all(list(g) == sorted(g) for g in groups)
        shares = load[groups].sum(-1)
        assert (shares.max() - shares.min()) / shares.mean() < 0.01, shares
    drawn = np.round(2048 * np.random.default_rng(6).lognormal(0.0, 0.35, size=(200, 32))).reshape(200, 4, 8).sum(-1)
    assert np.std(drawn[:, 0] / drawn.mean(-1)) > 0.03
    assert driver.balanced_groups(np.ones(8), 2).tolist() == [0, 2, 4, 6, 1, 3, 5, 7]  # ties: by number, in turn


def test_placing_experts_renumbers_the_router_s_outputs_and_nothing_else():
    import jax

    ref = common.load_module("reference", "lfm2_ppo")
    config = common.load_json(CHIP, "configs", "lfm2_8b_a1b_ep4.json")
    s = ref.sizes_from({**config["sizes"], **config["rehearse_sizes"]})
    params = ref.make_params(ref.param_spec(s), 3)
    where = np.stack([np.random.default_rng(i).permutation(s["num_experts"]) for i in range(4)]).astype(np.int32)
    placed = ref.place_experts(params, where, s)
    flat, flat_placed = (dict(jax.tree_util.tree_flatten_with_path(p)[0]) for p in (params, placed))
    moved = {k for k in flat if not np.array_equal(flat[k], flat_placed[k])}
    assert {jax.tree_util.keystr(k).split("']['")[-1][:-2] for k in moved} == {"router", "bias"} and len(moved) == 8
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, s["vocab"])
    with jax.default_matmul_precision("highest"):
        drawn, renumbered = ref.forward(params, tokens, s)[1], ref.forward(placed, tokens, s)[1]
    # the first expert layer sees the same state: the same experts are chosen, under their new numbers
    assert np.array_equal(np.sort(where[0][np.asarray(renumbered[0])], -1), np.sort(np.asarray(drawn[0]), -1))
    same = ref.place_experts(params, np.tile(np.arange(s["num_experts"]), (4, 1)), s)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree_util.tree_leaves(same), jax.tree_util.tree_leaves(params)))


# ------------------------------------------------------------------ the run itself, rehearsed


@pytest.mark.timeout(900)
def test_rehearsal_through_the_entry_point_exits_3_and_is_correct():
    """The command the driver runs, with ``--rehearse-cpu``: the whole ``--trace 0`` path at tiny sizes
    (set-up, three compared steps, the window, the reference, the comparison). Exit 3, never a pass; the
    result goes to standard error and every compared number is the rounding of float32."""
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
    limits = common.load_json(CHIP, "configs", "lfm2_8b_a1b_ep4.json")["limits"]
    assert set(result["compared"]) == set(limits) and result["compared"]["tokens_wrong"]["value"] == 0
    assert all(v["value"] < TEST_LIMIT for v in result["compared"].values()), result["compared"]


def _run(monkeypatch, fault=None, trace=False):
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
            devices=jax.devices()[:1], t_start=time.perf_counter(), out_dir=os.path.join(CHIP, "out"),
        )


@pytest.mark.timeout(900)
def test_three_steps_equal_the_reference_and_the_control_is_refused(monkeypatch):
    """Losses, the first gradient element by element, the parameters' change after three optimizer
    steps and the routing agree with the plain reference; the reference in bfloat16 (the nearest
    precision below the float32 this test runs in), put in the program's place, does not."""
    check = common.load_module("", "check_seq")
    probes = []
    compare = check.Probe.compare
    monkeypatch.setattr(check.Probe, "compare", lambda self, config: probes.append(self) or compare(self, config))
    out = _run(monkeypatch)
    verdict = out["check"]
    assert verdict["correct"] and not verdict["missing"], verdict
    assert out["steps"]["in_window"] > 0 and out["failed"] == 0
    assert out["counters"]["Moe/pairs_total"] == 2 * 32 * 2 * 4  # tokens x experts a token x expert layers
    assert 0 < out["counters"]["Moe/pairs_here"] < out["counters"]["Moe/pairs_total"]
    assert out["compile"]["at_window_end"]["retraces"] == out["compile"]["at_window_start"]["retraces"]
    probe = probes[0]
    where = out["placement"]  # the experts placed by the pool's load: every expert once a layer, and not as drawn
    assert np.array_equal(np.sort(where, -1), np.tile(np.arange(8), (4, 1))) and not np.array_equal(where, np.sort(where, -1))

    def fake_bf16(x):
        import jax
        import jax.numpy as jnp

        return x + jax.lax.stop_gradient(x.astype(jnp.bfloat16).astype(jnp.float32) - x)

    numbers = check.gaps(probe.reference_readings(quant=fake_bf16), probe.reference_readings())
    control = check.judge({"tokens_wrong": 0.0, **numbers}, _cell()["config_file"]["limits"])
    assert not control["correct"] and "grad_diff_leaf.experts" in _failed(control), control["compared"]


@pytest.mark.timeout(1200)
@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged", "expert_left_out", "three_experts"])
def test_judge_refuses_each_fault_planted_under_the_timed_path(monkeypatch, fault):
    verdict = _run(monkeypatch, fault=fault)["check"]
    assert verdict["compared"]["tokens_wrong"]["value"] == 0  # the feed is sound: the fault is the program's
    assert not verdict["correct"], (fault, verdict["compared"])
    failed = _failed(verdict)
    assert "grad_diff.experts" in failed and "delta_gap.experts" in failed, (fault, failed)


@pytest.mark.timeout(900)
def test_traced_path_with_the_device_plane_stubbed_reads_every_metric_it_can(monkeypatch):
    """``--trace 1`` on the CPU: the capture has no device plane, so ``reduce.reduce_dir`` is stubbed (the
    verify skill's recipe); the rest is the driver's own path: the program's text written beside the
    capture, the scope reduction (nothing to read: None), and every reader called without raising."""
    reduce = common.load_module("", "reduce")
    monkeypatch.setattr(reduce, "reduce_dir", lambda d: {"busy_s": 0.3, "window_s": 0.5, "n_devices": 1, "breakdown": {"device_ops": [], "idle_gaps": []}})
    run = _run(monkeypatch, trace=True)
    run.update(peak=None, cell=_cell())
    assert run["scopes"] is None and run["trace"]["breakdown"]["device_ms_a_step_by_scope"] == []
    values = {m["name"]: common.load_module("metrics", m["name"]).read(run) for m in run["cell"]["per_layer"]}
    assert values["rollout_feed_ms"] > 0 and values["rollout_feed_ms"] <= values["sample_wait_ms"]  # the span inside the benchmark's
    assert values["moe_load_max_over_mean"] >= 1.0 and values["window_compiles"] == 0
    assert values["moe_compact_share"] == run["counters"]["Moe/compact_share"] and 0.0 <= values["moe_compact_share"] <= 1.0
    assert values["train_route_ms"] + values["train_execute_ms"] <= values["dispatch_ms"]
    for name in ("lm_train_mfu_pct", "moe_device_ms", "mixer_device_ms", "head_loss_device_ms", "moe_experts_roofline_pct", "flash_attention_roofline_pct", "gmm_roofline_pct"):
        assert values[name] is None  # device numbers: nothing on a CPU
    # with a scope reduction and a peak, the shares read as numbers under 100
    run["peak"] = common.peak_for("TPU v5 lite")
    run["config"] = common.load_json(CHIP, "configs", "lfm2_8b_a1b_ep4.json")
    run["counters"]["Moe/pairs_here"] = 65536.0
    run["scopes"] = {"steps": 10.0, "scopes": {"lm.moe.experts": 0.5, "lm.moe.route": 0.2, "lm.attn": 0.9, "lm.conv": 0.4, "lm.head": 0.3, "ppo.loss": 0.05}, "kernels": {"lm.attn": 0.6, "lm.moe.experts": 0.4}}
    run["steps"]["in_window"], run["window_s"] = 10, 4.0
    again = {m["name"]: common.load_module("metrics", m["name"]).read(run) for m in run["cell"]["per_layer"]}
    assert again["moe_device_ms"] == pytest.approx(70.0) and again["mixer_device_ms"] == pytest.approx(130.0) and again["head_loss_device_ms"] == pytest.approx(35.0)
    assert again["moe_experts_roofline_pct"] == pytest.approx(100 * (4.329e12 / 197e12) / 0.05, rel=1e-2)
    assert again["flash_attention_roofline_pct"] == pytest.approx(100 * (1.649e12 / 197e12) / 0.06, rel=1e-2)
    assert again["gmm_roofline_pct"] == pytest.approx(100 * (4.329e12 / 197e12) / 0.04, rel=1e-2)
    assert again["lm_train_mfu_pct"] == pytest.approx(100 * 21.26e12 * 10 / 4.0 / 197e12, rel=1e-2)
