"""``scopes.py`` on the CPU: the scopes it looks for are each configuration's count file's
(and the program's own names), its arithmetic on small synthetic lists for one-word and for
dotted scopes, the seven readers of PR 27 that read the program's own spans and counters, and
``dv3.train``'s named scopes (metadata only: the same bits come out, and the lowered program
carries every name).
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, CHIP)

import common  # noqa: E402

scopes = common.load_module("", "scopes")
flops = common.load_module("", "flops")
NEW_METRICS = (
    "prefetch_wait_ms", "prefetch_sample_ms", "prefetch_h2d_ms", "h2d_mib_per_step",
    "train_route_ms", "train_execute_ms", "setup_lower_s",
)


def _program_scopes(name):
    if name == "dv3_xl_crafter":
        from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import TRAIN_SCOPES

        return tuple(TRAIN_SCOPES)
    from sheeprl_tpu.models import lm

    return tuple(lm.SCOPES) + ("ppo.loss", "ppo.opt")  # ppo_recurrent.train's own two, around the model's


@pytest.mark.parametrize("name", ["dv3_xl_crafter", "lfm2_8b_a1b_ep4"])
def test_every_counted_part_is_a_scope_of_the_program(name):
    """What the reduction looks for is data: the parts the configuration's count file counts, then the
    ones it lists as uncounted; for both accepted configurations that is the program's own tuple."""
    config = common.load_json(CHIP, "configs", f"{name}.json")
    module, count = flops.count_of(config)
    parts = tuple(k for k in count(config["sizes"]) if k != "total")
    assert parts and flops.scopes_of(config) == parts + tuple(module.UNCOUNTED) == _program_scopes(name)
    assert set(flops.scopes_of(config)) <= set(scopes.known_scopes())  # and what is looked for where no configuration is named
    for layer, names in getattr(module, "LAYERS", {}).items():
        assert set(names) <= set(flops.scopes_of(config)), layer


def test_self_time_gives_a_while_only_what_its_body_leaves():
    # a while covering its two body events, a gap inside it, then an event of its own
    ops = [("while", 0.0, 10.0), ("a", 1.0, 4.0), ("b", 4.0, 6.0), ("inner", 4.5, 5.0), ("c", 12.0, 13.0)]
    assert scopes.segments(ops) == [
        ("while", 0.0, 1.0), ("a", 1.0, 4.0), ("b", 4.0, 4.5), ("inner", 4.5, 5.0), ("b", 5.0, 6.0),
        ("while", 6.0, 10.0), ("c", 12.0, 13.0),
    ]
    times = scopes.self_times(ops)
    assert times == {"while": 5.0, "a": 3.0, "b": 1.5, "inner": 0.5, "c": 1.0}
    assert sum(times.values()) == sum(b - a for a, b in scopes.reduce.union((a, b) for _, a, b in ops))
    assert scopes.self_times(ops, 3.0, 12.5) == {"a": 1.0, "b": 1.5, "inner": 0.5, "while": 4.0, "c": 0.5}
    # an event that outlasts the one it started in keeps its time
    assert scopes.self_times([("p", 0.0, 2.0), ("q", 1.0, 3.0)]) == {"p": 1.0, "q": 2.0}


HLO = """HloModule jit_train, is_scheduled=true
%fused_computation.1 (p: f32[4]) -> f32[4] {
  ROOT %add.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(train)/while/body/jvp(encoder)/add" stack_frame_id=3}
}
ENTRY %main {
  %while.2 = (s32[], f32[4]{0}) while(%tuple), condition=%c, body=%b, metadata={op_name="jit(train)/while" stack_frame_id=1}
  %fusion.3 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train)/while/body/jvp(encoder)/add" stack_frame_id=3}
  %convolution.4 = f32[4]{0} convolution(%x, %w), metadata={op_name="jit(train)/while/body/transpose(jvp(dynamic_scan))/while/body/dot_general"}
  %fusion.5 = f32[4]{0} fusion(%y), kind=kLoop, calls=%f, metadata={op_name="jit(train)/while/body/transpose(jvp(critic_update))/target_critic/mul"}
  %copy.6 = f32[4]{0} copy(%z)
}
"""


def test_scope_of_an_event_comes_from_the_program_text():
    program, table = scopes.op_names(HLO)
    assert program == "jit_train"
    assert table["fusion.3"].endswith("jvp(encoder)/add") and "copy.6" not in table
    assert scopes.scope_of(table["fusion.3"]) == "encoder"
    assert scopes.scope_of(table["convolution.4"]) == "dynamic_scan"
    assert scopes.scope_of(table["fusion.5"]) == "target_critic"  # the innermost
    assert scopes.scope_of(table["while.2"]) == scopes.UNSCOPED and scopes.scope_of("") == scopes.UNSCOPED


def test_summarize_sums_to_busy_and_labels_gaps_by_the_innermost_span():
    _, table = scopes.op_names(HLO)
    modules = [("jit_train", 1.0, 5.0), ("jit_split", 5.5, 6.0), ("jit_train", 7.0, 11.0)]
    ops = [
        ("while.2", 1.0, 5.0), ("fusion.3", 1.0, 2.0), ("convolution.4", 2.0, 4.5), ("copy.6", 4.5, 5.0),
        ("fusion.9", 5.5, 6.0),
        ("while.2", 7.0, 11.0), ("fusion.3", 7.0, 8.0), ("convolution.4", 8.0, 10.5), ("fusion.5", 10.5, 11.0),
    ]
    host = {
        "python": [("train.call", 0.0, 7.5), ("prefetch.get", 0.0, 0.4), ("dv3.train.execute", 0.4, 1.2),
                   ("train.fence", 1.2, 5.2), ("player.push", 5.2, 6.9), ("train.call", 6.9, 10.0)],
        "worker": [("prefetch.sample", 5.0, 5.2)],
    }
    out = scopes.summarize(ops, modules, host, {"jit_train": table})
    assert out["window_s"] == 10.0 and out["busy_s"] == pytest.approx(7.5)
    assert out["scopes"] == pytest.approx(
        {"encoder": 2.0, "dynamic_scan": 4.5, "unscoped": 0.5, "other_programs": 0.5}
    )  # the second run is cut at the window's end, before fusion.5
    assert sum(out["scopes"].values()) == pytest.approx(out["busy_s"])
    assert out["unscoped_ops"] == [["copy.6", pytest.approx(0.5), ""]] and out["kernels"] == {}  # no path, no kernel
    assert dict(map(tuple, out["top_ops"]["dynamic_scan"])) == pytest.approx({"convolution.4": 4.5})
    assert out["steps"] == pytest.approx(1.75)
    # gaps 0-1, 5-5.5, 6-7, labelled thread by thread and by the innermost span: execute covers 0.6 of
    # the first, player.push most of the others; never the train.call around them
    assert out["idle_gaps"]["python"] == pytest.approx({"dv3.train.execute": 1.0, "player.push": 1.5})
    assert out["idle_gaps"]["worker"] == pytest.approx({"other": 2.5})  # covers 0.2 of one gap
    assert out["idle_gaps_over_1ms"] == out["idle_gaps"]
    with pytest.raises(ValueError):
        scopes.summarize(ops, modules, {}, {"jit_train": table})
    # a driver's reduction: the same sums by a configuration's own scopes, the idle gaps by thread left out
    names = flops.scopes_of(common.load_json(CHIP, "configs", "dv3_xl_crafter.json"))
    brief = scopes.summarize(ops, modules, host, {"jit_train": table}, names, idle_by_thread=False)
    assert brief["scopes"] == out["scopes"] and brief["busy_s"] == out["busy_s"] and "idle_gaps" not in brief
    assert scopes.summarize(ops, modules, host, {"jit_train": table}, ("encoder",))["scopes"] == pytest.approx(
        {"encoder": 2.0, "unscoped": 5.0, "other_programs": 0.5}
    )  # a scope that is not looked for is not found


def test_scope_of_reads_dotted_scopes_innermost_last():
    path = "jit(train)/jit(main)/while/body/ppo.loss/transpose(jvp(lm.moe.experts))/ragged_dot"
    assert scopes.scope_of(path) == "lm.moe.experts"
    assert scopes.scope_of("jit(train)/while/body/ppo.loss/checkpoint/lm.attn/dot_general") == "lm.attn"
    assert scopes.scope_of("jit(train)/while/body/ppo.opt/mul") == "ppo.opt"
    assert scopes.scope_of("jit(train)/while/body/ppo.loss/jvp(lm.moe.route)/top_k") == "lm.moe.route"
    assert scopes.scope_of("jit(train)/convert_element_type") == "unscoped"
    # a dotted name reads whole: neither its words nor a longer name stand for it
    assert scopes.scope_of("jit(train)/lm.attn.cache/mul") == "unscoped" and scopes.scope_of("jit(train)/lm/attn/mul") == "unscoped"
    assert scopes.scope_of("jit(train)/lm.attn/mul", ("lm.conv",)) == "unscoped" and scopes.scope_of("jit(train)/lm.extra/mul", ("lm.extra",)) == "lm.extra"
    text = (
        'HloModule jit_train\n'
        '  %fusion.3 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(train)/lm.conv/mul"}\n'
        '  %custom-call.7 = bf16[8,8]{1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(train)/lm.attn/pallas_call"}\n'
    )
    assert scopes.kernel_instructions(text) == {"custom-call.7"}
    # a step of two ops: the kernel's time is counted under its scope, and apart
    ops = [("fusion.3", 0.0, 0.3), ("custom-call.7", 0.4, 1.0)]
    table = scopes.op_names(text)
    summary = scopes.summarize(ops, [("jit_train", 0.0, 1.0)], {"t": [("train", 0.0, 1.0)]}, {table[0]: table[1]}, kernels={table[0]: {"custom-call.7"}})
    assert summary["scopes"] == pytest.approx({"lm.conv": 0.3, "lm.attn": 0.6}) and summary["kernels"] == pytest.approx({"lm.attn": 0.6})
    run = {"scopes": summary, "config": common.load_json(CHIP, "configs", "lfm2_8b_a1b_ep4.json")}
    assert scopes.scope_ms(run, "lm.conv", "lm.attn") == pytest.approx(900.0) and scopes.kernel_ms(run, "lm.attn") == pytest.approx(600.0)
    assert scopes.scope_ms({}, "lm.conv") is None and scopes.kernel_ms(run, "lm.conv") is None
    assert scopes.ms_a_step(summary) == [["lm.attn", pytest.approx(600.0)], ["lm.conv", pytest.approx(300.0)]] and scopes.ms_a_step(None) == []
    # by layer and by kernel family, both from the configuration's count file; nothing where it lists nothing
    assert scopes.layer_ms(run, "token mixers") == pytest.approx(900.0) and scopes.layer_ms(run, "expert layer") is None
    assert scopes.layer_ms(run, "no such layer") is None and scopes.roofline_pct(run, "attention") is None  # no peak
    run["peak"] = common.peak_for("TPU v5 lite")
    assert scopes.roofline_pct(run, "attention") == pytest.approx(100 * (1.649e12 / 197e12) / 0.6, rel=1e-3)
    assert scopes.roofline_pct(run, "gmm") is None and scopes.roofline_pct(run, "no such family") is None  # no kernel ran under its scope


def _run(attempted=4):
    def stats(calls, route, execute, lower):
        return {
            "lower_seconds": lower,
            "functions": {
                "dv3.train": {"calls": calls, "route_seconds": route, "execute_seconds": execute},
                "sync.unravel": {"calls": 1, "route_seconds": 9.0, "execute_seconds": 9.0},
            },
        }

    return {
        "attempted": attempted,
        "steps": {"in_window": 4},
        "compile": {"at_window_start": stats(3, 0.3, 0.6, 7.5), "at_window_end": stats(3 + attempted, 0.3 + 0.02, 0.6 + 0.008, 7.5)},
        "cell": {"here": CHIP},
    }


def _read(name, run):
    return common.load_module("metrics", name).read(run)


def test_new_readers_on_a_hand_made_run_and_ring():
    from sheeprl_tpu.telemetry import trace

    trace.disable()
    trace.follow_captures(None)
    run = _run()
    # no ring (a --trace 0 run, or the parent of PR 27): the span readers find nothing to read
    for name in NEW_METRICS[:4]:
        assert _read(name, run) is None, name
    try:
        tracer = trace.configure(plane="train", trace_id="readers")
        assert all(_read(name, run) is None for name in NEW_METRICS[:4])  # an empty ring
        for name, seconds, args in (
            ("prefetch.get", 0.001, {"served": "piece"}), ("prefetch.get", 0.003, {"served": "speculated"}),
            ("prefetch.sample", 0.040, {"n_samples": 4}), ("prefetch.h2d", 0.010, {"bytes": 8 * 2**20}),
            ("prefetch.h2d_fence", 0.006, {}), ("player.push", 0.5, {"skipped": "rebind"}),
        ):
            trace.add_span(name, 10.0, 10.0 + seconds, **args)
        trace.instant("prefetch.get")  # not a span: not read
        assert tracer.stats()["Telemetry/spans_recorded"] == 7
        assert _read("prefetch_wait_ms", run) == pytest.approx(1.0)
        assert _read("prefetch_sample_ms", run) == pytest.approx(10.0)
        assert _read("prefetch_h2d_ms", run) == pytest.approx(4.0)
        assert _read("h2d_mib_per_step", run) == pytest.approx(2.0)
    finally:
        trace.disable()
    assert _read("train_route_ms", run) == pytest.approx(5.0)
    assert _read("train_execute_ms", run) == pytest.approx(2.0)
    assert _read("setup_lower_s", run) == 7.5
    # a program that keeps no such counters (the parent): nothing to read, nothing raised
    for snapshot in run["compile"].values():
        del snapshot["lower_seconds"]
        for fn in snapshot["functions"].values():
            del fn["route_seconds"], fn["execute_seconds"]
    assert [_read(n, run) for n in NEW_METRICS[4:]] == [None, None, None]


# ---------------------------------------------------------------- the named scopes of dv3.train


@pytest.mark.timeout(900)
def test_dv3_train_scopes_change_no_bit_and_reach_the_lowered_program(monkeypatch):
    import contextlib
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu import cli
    from sheeprl_tpu.algos.dreamer_v3 import agent
    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.core.runtime import build_runtime
    from sheeprl_tpu.utils.utils import DreamerPlayerSync

    learner = common.load_module("drivers", "learner")
    config = common.load_json(CHIP, "configs", "dv3_xl_crafter.json")
    overrides = list(config["overrides"]) + list(config["rehearse_overrides"]) + ["seed=7", "fabric.precision=32-true"]
    cfg = compose(config_name="config", overrides=overrides)
    cli._apply_global_flags(cfg)
    runtime = build_runtime(cfg.fabric)
    obs_space, actions_dim, is_continuous = learner.spaces_of(config)
    modules, params, _player = agent.build_agent(runtime, actions_dim, is_continuous, cfg, obs_space)
    psync = DreamerPlayerSync(runtime, params, wm_keys=dv3.PLAYER_WM_KEYS, every=1)  # host player: the ravel is in the program
    assert psync.enabled

    t, b = int(cfg.algo.per_rank_sequence_length), int(cfg.algo.per_rank_batch_size)
    rng = np.random.default_rng(0)
    batches = {
        "rgb": rng.integers(0, 255, (1, t, b, 3, 64, 64), dtype=np.uint8),
        "reward": rng.normal(size=(1, t, b, 1)).astype(np.float32),
        "rewards": rng.normal(size=(1, t, b, 1)).astype(np.float32),
        "actions": np.eye(17, dtype=np.float32)[rng.integers(0, 17, (1, t, b))],
        "terminated": np.zeros((1, t, b, 1), np.float32),
        "truncated": np.zeros((1, t, b, 1), np.float32),
        "is_first": np.zeros((1, t, b, 1), np.float32),
    }
    key = jax.random.PRNGKey(3)

    def one_call(scope):
        monkeypatch.setattr(dv3, "_scope", scope)
        init_opt, train_fn = dv3.make_train_fn(modules, cfg, runtime, is_continuous, actions_dim, psync)
        fresh = jax.tree_util.tree_map(jnp.array, params)  # the call donates its state
        args = (fresh, init_opt(fresh), init_moments(), jnp.int32(0), batches, key)
        text = jax.jit(train_fn.fun).lower(*args).as_text(debug_info=True)
        return jax.tree_util.tree_map(np.asarray, train_fn(*args)), text

    scoped, scoped_text = one_call(jax.named_scope)
    plain, plain_text = one_call(lambda name: contextlib.nullcontext())
    leaves_a, tree_a = jax.tree_util.tree_flatten(scoped)
    leaves_b, tree_b = jax.tree_util.tree_flatten(plain)
    assert tree_a == tree_b and len(leaves_a) > 100
    for x, y in zip(leaves_a, leaves_b):
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes()
    for name in dv3.TRAIN_SCOPES:  # as a step of an op's path: "jit(train)/encoder/...", "jvp(encoder)", "moments/..."
        on_a_path = re.compile(rf'["/(]{name}[/)]')
        assert on_a_path.search(scoped_text), name
        assert not on_a_path.search(plain_text), name
