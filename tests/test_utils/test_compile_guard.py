"""Retrace guard + AOT routing unit tests (core/compile.py).

Covers the perf contract the train loops rely on: a warmed signature never
traces, a drifting signature is counted and diffed, and ``guard.policy=halt``
turns post-steady drift into a hard error instead of a silent recompile storm.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.core import compile as jax_compile


@pytest.fixture(autouse=True)
def _reset_guard_state():
    # policy/steady watermark are process-wide: restore the defaults so test
    # order never leaks a `halt` policy into unrelated tests
    jax_compile.configure({})
    yield
    jax_compile.configure({})


def test_first_compile_is_not_a_retrace():
    gfn = jax_compile.guarded_jit(lambda x: x * 2, name="t.first")
    out = gfn(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert gfn.traces == 1
    assert gfn.retraces == 0


def test_shape_drift_counts_retraces_and_logs_diff(caplog):
    gfn = jax_compile.guarded_jit(lambda x: x + 1, name="t.drift")
    gfn(jnp.ones((4,)))
    with caplog.at_level(logging.WARNING, logger="sheeprl_tpu.compile"):
        gfn(jnp.ones((8,)))
    assert gfn.retraces == 1
    assert gfn.last_diff is not None
    assert "(4,)" in gfn.last_diff and "(8,)" in gfn.last_diff
    assert any("retrace" in rec.message for rec in caplog.records)
    # same shapes again: served from jit's cache, no new trace
    calls_before = gfn.traces
    gfn(jnp.ones((8,)))
    assert gfn.traces == calls_before


def test_dtype_drift_is_diffed():
    gfn = jax_compile.guarded_jit(lambda x: x + 1, name="t.dtype")
    gfn(jnp.ones((4,), jnp.float32))
    gfn(jnp.ones((4,), jnp.int32))
    assert gfn.retraces == 1
    assert "float32" in gfn.last_diff and "int32" in gfn.last_diff


def test_halt_policy_raises_after_steady():
    jax_compile.configure({"compile": {"guard": {"policy": "halt"}}})
    gfn = jax_compile.guarded_jit(lambda x: x * 3, name="t.halt")
    gfn(jnp.ones((4,)))
    jax_compile.mark_steady()
    with pytest.raises(jax_compile.RetraceError):
        gfn(jnp.ones((16,)))


def test_warn_policy_never_raises_after_steady():
    gfn = jax_compile.guarded_jit(lambda x: x * 3, name="t.warn")
    gfn(jnp.ones((4,)))
    jax_compile.mark_steady()
    gfn(jnp.ones((16,)))  # logs, but must not raise
    assert gfn.retraces == 1


def test_aot_route_never_traces():
    gfn = jax_compile.guarded_jit(lambda x: x @ x, name="t.aot")
    gfn.aot_compile(jax.ShapeDtypeStruct((3, 3), jnp.float32))
    assert gfn.aot_compiles == 1
    out = gfn(jnp.eye(3))
    np.testing.assert_allclose(np.asarray(out), np.eye(3))
    assert gfn.traces == 0
    assert gfn.calls == 1


def test_aot_route_accepts_weak_typed_inputs():
    # jnp.full with a python float builds a weak-typed array; the router must
    # still hit the strong-typed executable (weak_type is erased from the key)
    gfn = jax_compile.guarded_jit(lambda x: x + x, name="t.weak")
    gfn.aot_compile(jax.ShapeDtypeStruct((3, 3), jnp.float32))
    gfn(jnp.full((3, 3), 2.0))
    assert gfn.traces == 0


def test_unwarmed_shape_falls_back_to_jit_and_counts_retrace():
    gfn = jax_compile.guarded_jit(lambda x: x + 1, name="t.fallback")
    gfn.aot_compile(jax.ShapeDtypeStruct((4,), jnp.float32))
    # a shape the warmup did not cover: correctness first (jit path), but the
    # guard flags it — this is exactly the drift the AOT specs must prevent
    out = gfn(jnp.ones((5,)))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert gfn.traces == 1
    assert gfn.retraces == 1


def test_guarded_jit_static_argnames():
    def f(x, flag):
        return x * 2 if flag else x

    gfn = jax_compile.guarded_jit(f, name="t.static", static_argnames=("flag",))
    np.testing.assert_allclose(np.asarray(gfn(jnp.ones(()), True)), 2.0)
    np.testing.assert_allclose(np.asarray(gfn(jnp.ones(()), False)), 1.0)
    assert gfn.traces == 2  # one per static value: expected, both are first compiles per branch


def test_drain_compile_counters_reports_delta():
    gfn = jax_compile.guarded_jit(lambda x: x + 1, name="t.drain")
    gfn(jnp.ones((2,)))
    gfn(jnp.ones((3,)))
    jax_compile.drain_compile_counters(None)  # snapshot
    delta = jax_compile.drain_compile_counters(None)
    assert delta["Compile/retraces"] == 0.0
    gfn(jnp.ones((7,)))
    delta = jax_compile.drain_compile_counters(None)
    assert delta["Compile/retraces"] == 1.0


def test_signature_excludes_committed_device_but_keeps_structure():
    gfn = jax_compile.guarded_jit(lambda tree: tree["a"] + tree["b"], name="t.tree")
    gfn.aot_compile({"a": jax.ShapeDtypeStruct((2,), jnp.float32), "b": jax.ShapeDtypeStruct((2,), jnp.float32)})
    gfn({"a": jnp.ones((2,)), "b": jnp.ones((2,))})
    assert gfn.traces == 0
    # different pytree structure: distinct signature, routed to the jit path
    gfn({"a": jnp.ones((2,)), "b": jnp.ones((2,)), "c": jnp.ones((2,))})


def test_pow2_bucket():
    assert jax_compile.pow2_bucket(0) == 1
    assert jax_compile.pow2_bucket(1) == 1
    assert jax_compile.pow2_bucket(3) == 4
    assert jax_compile.pow2_bucket(4) == 4
    assert jax_compile.pow2_bucket(9) == 16
    assert jax_compile.pow2_bucket(2, minimum=8) == 8


def test_bucketed_pad_shapes_and_mask():
    chunks = {
        "obs": [np.ones((3, 5), np.float32), np.ones((2, 5), np.float32), np.ones((4, 5), np.float32)],
        "rew": [np.ones((3, 1), np.float32), np.ones((2, 1), np.float32), np.ones((4, 1), np.float32)],
    }
    out = jax_compile.bucketed_pad(chunks, lengths=[3, 2, 4], length=4)
    assert out["obs"].shape == (4, 4, 5)  # [sl, pow2_bucket(3)=4, feat]
    assert out["rew"].shape == (4, 4, 1)
    assert out["mask"].shape == (4, 4, 1)
    np.testing.assert_array_equal(out["mask"][:, 0, 0], [1, 1, 1, 0])
    np.testing.assert_array_equal(out["mask"][:, 1, 0], [1, 1, 0, 0])
    np.testing.assert_array_equal(out["mask"][:, 3, 0], [0, 0, 0, 0])  # pure padding column
    assert out["obs"][3, 1].sum() == 0.0  # padded rows stay zero


def test_bucketed_pad_rejects_empty_and_ragged():
    with pytest.raises(ValueError):
        jax_compile.bucketed_pad({"x": []}, lengths=[], length=4)
    with pytest.raises(ValueError):
        jax_compile.bucketed_pad({"x": [np.ones((2, 1))]}, lengths=[2, 3], length=4)


def test_call_counters_split_route_execute_and_lower():
    """What `train_route_ms` / `train_execute_ms` / `setup_lower_s` read: the time of
    a call is split where the executable is known, an AOT compile where the program
    is lowered, and a call that compiled counts as compile time, not as execute time."""
    from sheeprl_tpu.telemetry import trace

    gfn = jax_compile.guarded_jit(lambda x: jnp.tanh(x @ x), name="t.counters")
    spec = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    before = jax_compile.process_stats()["lower_seconds"]
    gfn.aot_compile(spec)
    s0 = gfn.stats()
    assert 0.0 < s0["lower_seconds"] <= s0["compile_seconds"]
    assert s0["route_seconds"] == s0["execute_seconds"] == 0.0
    assert jax_compile.process_stats()["lower_seconds"] == pytest.approx(before + s0["lower_seconds"])
    tracer = trace.configure(plane="train", trace_id="counters")
    try:
        x = jnp.ones((16, 16), jnp.float32)
        seen = [s0]
        for _ in range(3):
            gfn(x)
            seen.append(gfn.stats())
        assert gfn.traces == 0
        for a, b in zip(seen, seen[1:]):
            assert b["route_seconds"] > a["route_seconds"] and b["execute_seconds"] > a["execute_seconds"]
            assert b["lower_seconds"] == a["lower_seconds"] and b["compile_seconds"] == a["compile_seconds"]
        assert jax_compile.process_stats()["functions"]["t.counters"]["route_seconds"] == seen[-1]["route_seconds"]
        names = [ev[trace._EV_NAME] for ev in tracer.events()]
        assert names == ["t.counters.route", "t.counters.execute"] * 3
        # the jit path: the call that traces is compile time; the next one is execute time
        gfn(jnp.ones((8, 8), jnp.float32))
        traced = gfn.stats()
        assert traced["compile_seconds"] > seen[-1]["compile_seconds"]
        assert traced["execute_seconds"] == seen[-1]["execute_seconds"]
        gfn(jnp.ones((8, 8), jnp.float32))
        assert gfn.stats()["execute_seconds"] > traced["execute_seconds"]
    finally:
        trace.disable()


def test_aot_compile_records_lower_and_compile_spans():
    from sheeprl_tpu.telemetry import trace

    tracer = trace.configure(plane="train", trace_id="aot")
    try:
        gfn = jax_compile.guarded_jit(lambda x: x + 1, name="t.aotspans")
        gfn.aot_compile(jax.ShapeDtypeStruct((4,), jnp.float32))
        assert [ev[trace._EV_NAME] for ev in tracer.events()] == ["t.aotspans.lower", "t.aotspans.compile"]
    finally:
        trace.disable()


# --------------------------------------------------------------------------- #
# The selector: a call is routed by the leaves that tell the executables apart
# --------------------------------------------------------------------------- #


def _count_full_routes(monkeypatch):
    seen = []
    real = jax_compile.abstract_signature

    def counted(args, kwargs):
        seen.append(1)
        return real(args, kwargs)

    monkeypatch.setattr(jax_compile, "abstract_signature", counted)
    return seen


def test_one_executable_is_called_without_reading_the_signature(monkeypatch):
    from sheeprl_tpu.telemetry import trace

    gfn = jax_compile.guarded_jit(lambda p, x: jax.tree_util.tree_map(lambda a: a + x.sum(), p), name="t.sel.one")
    params = {f"w{i}": jnp.full((4,), float(i)) for i in range(16)}
    gfn.aot_compile(jax_compile.specs_of(params), jax.ShapeDtypeStruct((3,), jnp.float32))
    full_routes = _count_full_routes(monkeypatch)
    tracer = trace.configure(plane="train", trace_id="selector")
    try:
        for _ in range(5):
            out = gfn(params, jnp.ones((3,)))
        events = [ev for ev in tracer.events() if ev[trace._EV_NAME] == "t.sel.one.route"]
    finally:
        trace.disable()
    np.testing.assert_allclose(np.asarray(out["w7"]), 10.0)
    assert full_routes == []
    assert gfn.traces == 0
    s = gfn.stats()
    assert (s["route_hits"], s["route_misses"]) == (5, 0)
    assert [ev[trace._EV_ARGS] for ev in events] == [{"hit": True}] * 5
    totals = jax_compile.process_stats()
    assert totals["route_hits"] >= 5 and totals["functions"]["t.sel.one"]["route_hits"] == 5


def test_two_executables_are_told_apart_by_the_leaf_that_differs(monkeypatch):
    def f(tree, scale):
        return tree["x"] @ tree["w"] * scale

    gfn = jax_compile.guarded_jit(f, name="t.sel.two")
    w = jnp.arange(12.0).reshape(3, 4)
    for rows in (2, 5):
        gfn.aot_compile({"x": jax.ShapeDtypeStruct((rows, 3), jnp.float32),
                         "w": jax.ShapeDtypeStruct((3, 4), jnp.float32)},
                        jax.ShapeDtypeStruct((), jnp.float32))
    _only, groups = gfn._selector
    assert [positions for positions, _ in groups.values()] == [(1,)]  # flat leaves: w, x, scale
    full_routes = _count_full_routes(monkeypatch)
    reference = jax.jit(f)
    for i in range(6):
        tree = {"x": jnp.full((2 if i % 2 else 5, 3), float(i)), "w": w}
        np.testing.assert_array_equal(np.asarray(gfn(tree, jnp.float32(0.5))),
                                      np.asarray(reference(tree, jnp.float32(0.5))))
    assert full_routes == []
    assert gfn.traces == 0
    assert (gfn.route_hits, gfn.route_misses, gfn.aot_fallbacks) == (6, 0, 0)


def test_a_signature_no_executable_holds_is_refused_undonated_then_traced(monkeypatch):
    gfn = jax_compile.guarded_jit(lambda x, y: (x * 2, y + 1), name="t.sel.none", donate_argnums=(0,))
    gfn.aot_compile(jax.ShapeDtypeStruct((4,), jnp.float32), jax.ShapeDtypeStruct((2,), jnp.float32))
    x, y = jnp.ones((4,)), jnp.ones((3,))
    donated_at_route = []
    real_route = gfn._route

    def spy(args, kwargs):
        donated_at_route.append(args[0].is_deleted())
        return real_route(args, kwargs)

    monkeypatch.setattr(gfn, "_route", spy)
    out_x, out_y = gfn(x, y)
    # the executable refused the call before anything ran: x was still whole
    # when the full route began; the jit path then served (and donated) it
    assert donated_at_route == [False]
    np.testing.assert_allclose(np.asarray(out_x), 2.0)
    np.testing.assert_allclose(np.asarray(out_y), 2.0)
    assert gfn.traces == 1
    assert (gfn.aot_fallbacks, gfn.route_hits, gfn.route_misses) == (0, 0, 1)
    assert gfn.last_signature[0][1][0] == (3,)
    assert len(gfn.aot_executables()) == 1  # a signature fault evicts nothing


def test_a_refused_placement_falls_back_once_and_evicts():
    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs two devices")
    from jax.sharding import SingleDeviceSharding

    gfn = jax_compile.guarded_jit(lambda x: x * 3, name="t.sel.placed")
    for n in (8, 4):  # the other executable stays: the selector must forget the evicted one
        gfn.aot_compile(jax.ShapeDtypeStruct((n,), jnp.float32, sharding=SingleDeviceSharding(devices[0])))
    key = next(key for key in gfn._aot if key[0][0][0] == (4,))
    exe = gfn._aot[key]
    dispatched = []

    def counted(*args, **kwargs):
        dispatched.append(1)
        return exe(*args, **kwargs)

    with jax_compile._LOCK:
        gfn._aot[key] = counted
        gfn._reselect()
    x = jax.device_put(jnp.ones((4,)), devices[1])  # committed elsewhere: same signature
    for _ in range(2):
        np.testing.assert_allclose(np.asarray(gfn(x)), 3.0)
    assert dispatched == [1]  # refused once, never called again
    assert gfn.aot_fallbacks == 1
    assert len(gfn.aot_executables()) == 1 and key not in gfn._aot
    assert gfn.traces == 1
    assert (gfn.route_hits, gfn.route_misses) == (0, 2)


@pytest.mark.parametrize("scalar", [2.0, jnp.asarray(2.0)], ids=["python", "weak_array"])
def test_a_weak_typed_scalar_is_served_by_the_pick(scalar):
    gfn = jax_compile.guarded_jit(lambda x, s: x * s, name="t.sel.weak")
    gfn.aot_compile(jax.ShapeDtypeStruct((3,), jnp.float32), jax.ShapeDtypeStruct((), jnp.float32))
    np.testing.assert_allclose(np.asarray(gfn(jnp.ones((3,)), scalar)), 2.0)
    assert gfn.traces == 0
    assert (gfn.route_hits, gfn.route_misses) == (1, 0)


@pytest.mark.parametrize("registered", [False, True], ids=["nothing_registered", "another_registered"])
def test_a_call_racing_a_pending_warmup_waits_for_it(registered):
    import threading

    gfn = jax_compile.guarded_jit(lambda x: x - 1, name="t.sel.pending")
    if registered:
        gfn.aot_compile(jax.ShapeDtypeStruct((2,), jnp.float32))
    release = threading.Event()
    warmup = jax_compile.AOTWarmup()
    warmup.add_task(release.wait, name="hold")
    warmup.add(gfn, jax.ShapeDtypeStruct((6,), jnp.float32))
    warmup.start()
    timer = threading.Timer(0.3, release.set)
    timer.start()
    try:
        np.testing.assert_allclose(np.asarray(gfn(jnp.ones((6,)))), 0.0)
    finally:
        timer.join()
        warmup.wait()
    assert gfn.traces == 0
    assert gfn.aot_compiles == 1 + registered
    assert (gfn.route_hits, gfn.route_misses, gfn.aot_fallbacks) == (0, 1, 0)
    gfn(jnp.ones((6,)))
    assert (gfn.route_hits, gfn.route_misses) == (1, 1)
