"""The record of set-up kept beside the compile counters (``core/compile.py``): phases as intervals,
JAX's own compile durations summed by the cache listener, and the trace-and-lower seconds that a
guarded call through plain ``jit`` books into ``lower_seconds``."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.telemetry import trace

TRACE, LOWER = jax_compile._TRACE_EVENTS
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
TOTALS = ("trace_seconds", "trace_count", "backend_compile_seconds", "backend_compile_count",
          "cache_retrieval_seconds", "cache_retrieval_count")


@pytest.fixture
def record(monkeypatch):
    """An empty record for the test; the process's own comes back after it."""
    rows = []
    monkeypatch.setattr(jax_compile, "_SETUP_PHASES", rows)
    monkeypatch.setattr(jax_compile, "_STEADY", False)  # an earlier test's watermark would keep nothing
    return rows


def _totals():
    stats = jax_compile.process_stats()
    return {k: stats[k] for k in TOTALS}


def test_phases_nest_end_in_order_and_sum_by_name(record):
    with jax_compile.setup_phase("build_agent"):
        with jax_compile.setup_phase("build_agent.init"):
            time.sleep(0.01)
        with jax_compile.setup_phase("build_agent.place"):
            time.sleep(0.01)

    @jax_compile.setup_phase("make_train_fn")
    def make(x):
        """a builder"""
        return x + 1

    assert make(1) == 2 and make(2) == 3 and make.__name__ == "make" and make.__doc__ == "a builder"
    stats = jax_compile.process_stats()
    assert [name for name, _, _ in stats["setup_phases"]] == [
        "build_agent.init", "build_agent.place", "build_agent", "make_train_fn", "make_train_fn"
    ]  # in the order they ended: a child before its parent
    (_, i0, i1), (_, p0, p1), (_, b0, b1), (_, m0, m1), (_, n0, n1) = stats["setup_phases"]
    assert b0 <= i0 < i1 <= p0 < p1 <= b1 <= m0 < m1 <= n0 < n1
    seconds = stats["setup_seconds"]
    assert seconds["build_agent"] >= seconds["build_agent.init"] + seconds["build_agent.place"] >= 0.02
    assert seconds["make_train_fn"] == pytest.approx((m1 - m0) + (n1 - n0))
    with pytest.raises(ZeroDivisionError):  # a phase that raises is still recorded, and the error goes on
        with jax_compile.setup_phase("compose"):
            1 / 0
    assert record[-1][0] == "compose"


def test_phases_are_spans_named_setup_with_a_tracer_configured(record):
    tracer = trace.configure(plane="train", trace_id="setupspans")
    try:
        with jax_compile.setup_phase("runtime"):
            with jax_compile.setup_phase("build_agent.init"):
                pass
        jax_compile.record_setup_phase("import", 1.0, 2.0)
        events = {ev[trace._EV_NAME]: ev for ev in tracer.events()}
    finally:
        trace.disable()
    assert set(events) == {"setup.runtime", "setup.build_agent.init", "setup.import"}
    assert events["setup.build_agent.init"][trace._EV_PARENT] == events["setup.runtime"][trace._EV_SID]
    assert events["setup.import"][trace._EV_DUR] == pytest.approx(1e6)  # on the ring's clock: perf_counter
    assert [name for name, _, _ in record] == ["build_agent.init", "runtime", "import"]


def test_jax_durations_are_summed_and_a_nested_trace_once():
    installed = len(jax._src.monitoring._event_duration_secs_listeners)
    jax_compile.install_cache_listeners()  # installed at import: a second install adds nothing
    assert len(jax._src.monitoring._event_duration_secs_listeners) == installed
    before = _totals()
    record = jax.monitoring.record_event_duration_secs
    record(BACKEND, 0.25)
    record(BACKEND, 0.5)
    record(CACHE_LOAD, 0.125)
    record(TRACE, 1.0)
    record(LOWER, 2.0)
    record("/jax/core/compile/some_other_duration", 100.0)  # not one of the four
    # a jit traced inside another: JAX records each interval's start as a scalar, the inner one ends first
    jax.monitoring.record_scalar(TRACE, time.time())
    jax.monitoring.record_scalar(TRACE, time.time())
    record(TRACE, 0.5)
    record(TRACE, 4.0)
    after = _totals()
    delta = {k: after[k] - before[k] for k in TOTALS}
    assert delta == pytest.approx({
        "trace_seconds": 7.0, "trace_count": 3, "backend_compile_seconds": 0.75, "backend_compile_count": 2,
        "cache_retrieval_seconds": 0.125, "cache_retrieval_count": 1,
    })


def test_a_compile_request_is_booked_once_by_how_it_was_served():
    """JAX times a whole compile request under ``backend_compile_duration``, a load from the persistent cache
    inside it: a request the cache answered is a cache load and no XLA compile, one it did not is XLA's."""
    before = _totals()
    record = jax.monitoring.record_event_duration_secs
    jax.monitoring.record_scalar(BACKEND, time.time())  # a request the cache answers
    record(CACHE_LOAD, 0.125)
    record(BACKEND, 0.375)
    jax.monitoring.record_scalar(BACKEND, time.time())  # a request it does not
    record(BACKEND, 2.0)
    delta = {k: _totals()[k] - before[k] for k in TOTALS}
    assert delta == pytest.approx({
        "trace_seconds": 0.0, "trace_count": 0, "backend_compile_seconds": 2.0, "backend_compile_count": 1,
        "cache_retrieval_seconds": 0.375, "cache_retrieval_count": 1,
    })
    assert jax_compile._open_requests() == []


def test_a_program_loaded_from_the_persistent_cache_is_no_xla_compile(tmp_path):
    """The same program compiled twice in a fresh process, the second time from a persistent cache that keeps
    every program: the second request is a cache load, and XLA's seconds are the first request's alone."""
    code = (
        "import json, jax, jax.numpy as jnp\n"
        "from sheeprl_tpu.core import compile as c\n"
        "keys = ('backend_compile_seconds', 'backend_compile_count', 'cache_retrieval_seconds', 'cache_retrieval_count')\n"
        "f = lambda x: jnp.sin(x) @ x.T + 1.0\n"
        "rows = []\n"
        "for _ in range(2):\n"
        "    jax.clear_caches()  # the second request goes to the persistent cache, not the process's own\n"
        "    jax.jit(f).lower(jnp.ones((16, 16))).compile()\n"
        "    s = c.process_stats(); rows.append({k: s[k] for k in keys})\n"
        "print(json.dumps(rows))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "SHEEPRL_TPU_COMP_CACHE_MIN_SECS": "0", "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    first, second = json.loads(proc.stdout.strip().splitlines()[-1])
    assert first["backend_compile_count"] >= 1 and first["cache_retrieval_count"] == 0
    # every program the first round compiled (the ones of `jnp.ones` too), the second loaded
    assert second["cache_retrieval_count"] == first["backend_compile_count"] and second["cache_retrieval_seconds"] > 0.0
    assert (second["backend_compile_count"], second["backend_compile_seconds"]) == (
        first["backend_compile_count"], first["backend_compile_seconds"])


def test_another_thread_s_events_count_for_the_process_not_for_this_thread():
    mine = jax_compile._thread_trace_seconds()
    before = _totals()["trace_seconds"]
    other = threading.Thread(target=jax.monitoring.record_event_duration_secs, args=(TRACE, 3.0))
    other.start()
    other.join()
    assert jax_compile._thread_trace_seconds() == mine
    assert _totals()["trace_seconds"] - before == pytest.approx(3.0)


def test_a_guarded_call_through_plain_jit_books_its_trace_and_lowering(record):
    """The jit path used to book a call that traced into ``compile_seconds`` alone, so ``setup_lower_s`` read 0
    in the cells whose train function is called that way; a warmup thread's events are not this call's."""

    def body(x):
        # while this call traces, another thread closes a trace of 10 s (an AOT warmup's, say)
        other = threading.Thread(target=jax.monitoring.record_event_duration_secs, args=(TRACE, 10.0))
        other.start()
        other.join()
        return jnp.tanh(x) @ x.T

    gfn = jax_compile.guarded_jit(body, name="t.setup_jit")
    gfn(jnp.ones((8, 8)))
    s = gfn.stats()
    assert s["aot_compiles"] == 0 and s["traces"] == 1
    assert 0.0 < s["lower_seconds"] <= s["compile_seconds"] < 10.0
    assert [(name, end - start) for name, start, end in record] == [("compile.t.setup_jit", pytest.approx(s["compile_seconds"]))]
    gfn(jnp.ones((8, 8)))  # a call that compiled nothing is no phase and no lowering
    assert len(record) == 1 and gfn.stats()["lower_seconds"] == s["lower_seconds"]


def test_an_aot_compile_and_the_first_wait_that_found_work_are_phases(record):
    gfn = jax_compile.guarded_jit(lambda x: x * 3.0, name="t.setup_aot")
    gate = threading.Event()
    warmup = jax_compile.AOTWarmup(enabled=True)
    warmup.add_task(gate.wait, name="gate")
    warmup.add(gfn, jax.ShapeDtypeStruct((4,), jnp.float32))
    warmup.start()
    threading.Timer(0.05, gate.set).start()
    assert warmup.wait() and warmup.wait()  # the second finds nothing left: no phase
    names = [name for name, _, _ in record]
    assert names == ["compile.t.setup_aot", "aot_warmup"]
    (_, c0, c1), (_, w0, w1) = record
    assert w0 < c0 < c1 <= w1 and gfn.stats()["compile_seconds"] == pytest.approx(c1 - c0)


def test_the_package_s_import_is_a_phase_of_a_fresh_process():
    code = (
        "import json, sys, time\n"
        "t = time.perf_counter()\n"
        "assert 'jax' not in sys.modules\n"
        "import sheeprl_tpu\n"
        "from sheeprl_tpu.core import compile as c\n"
        "print(json.dumps([t, time.perf_counter(), c.process_stats()['setup_phases']]))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("SHEEPRL_TPU_TRACE", None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    t0, t1, phases = json.loads(proc.stdout.strip().splitlines()[-1])
    assert [p[0] for p in phases] == ["import"]
    assert t0 <= phases[0][1] < phases[0][2] <= t1  # import jax included: the phase is most of the interval
    assert phases[0][2] - phases[0][1] > 0.5 * (t1 - t0)
