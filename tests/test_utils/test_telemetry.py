"""sheeprl_tpu/telemetry: tracer ring semantics, the zero-cost-when-disabled
guarantee, Chrome-trace schema, trace-id propagation into the health/failpoint/
checkpoint surfaces, the metrics fabric, and the no-host-traffic proof for
span recording around a warm fused iteration."""

import json
import os
import threading
import time

import pytest

from sheeprl_tpu.core import failpoints
from sheeprl_tpu.telemetry import device as tel_device
from sheeprl_tpu.telemetry import export as tel_export
from sheeprl_tpu.telemetry import registry as tel_registry
from sheeprl_tpu.telemetry import trace

pytestmark = pytest.mark.telemetry


@pytest.fixture(autouse=True)
def _clean_telemetry():
    trace.disable()
    tel_registry.clear()
    failpoints.reset()
    yield
    trace.disable()
    tel_registry.clear()
    failpoints.reset()


# --------------------------------------------------------------------------- #
# the production guarantee: disabled means ONE None-check, nothing else
# --------------------------------------------------------------------------- #


def test_disabled_tracing_never_reaches_the_recording_layer(monkeypatch):
    def boom(*a, **k):  # any recording work while disabled is a perf regression
        raise AssertionError("instrumentation reached past the `_tracer is None` guard")

    monkeypatch.setattr(trace, "_begin", boom)
    monkeypatch.setattr(trace, "_record_instant", boom)
    monkeypatch.setattr(trace, "_record_span", boom)
    assert trace.span("train/update", iter=1) is trace._NOOP
    assert trace.instant("whatever", x=1) is None
    assert trace.add_span("serve/request", 0.0, 1.0, status="ok") is None
    assert trace.new_span_id() == ""
    assert trace.current_trace_id() == ""
    assert trace.current_span_id() == ""
    assert not trace.enabled()


def test_disabled_span_is_a_shared_singleton():
    a = trace.span("x")
    b = trace.span("y", plane="serve", anything=3)
    assert a is b is trace._NOOP  # no allocation on the disabled path
    with a as sp:  # and it supports the full live-span surface
        assert sp.set(k=1) is sp
        assert sp.span_id == "" and sp.trace_id == ""
    assert trace.stats() == {"Telemetry/enabled": 0}
    assert trace.export() is None


def test_disabled_setup_phase_keeps_nothing_beyond_its_row(monkeypatch):
    """A set-up phase with tracing off: the no-op span, two clock reads and one row of the record. Of what a
    thousand phases allocate, all that stays is made on the lines that make the row; the rest is freed
    within its call."""
    import inspect
    import tracemalloc

    from sheeprl_tpu.core import compile as jax_compile

    def boom(*a, **k):
        raise AssertionError("a disabled setup phase reached the recording layer")

    monkeypatch.setattr(trace, "_begin", boom)
    monkeypatch.setattr(trace, "_record_span", boom)
    rows = []
    monkeypatch.setattr(jax_compile, "_SETUP_PHASES", rows)
    monkeypatch.setattr(jax_compile, "_STEADY", False)
    row_lines = set()  # the two clock reads and the append
    for method in (jax_compile.setup_phase.__enter__, jax_compile.setup_phase.__exit__, jax_compile.record_setup_phase):
        lines, first = inspect.getsourcelines(method)
        row_lines |= {first + i for i, line in enumerate(lines) if "perf_counter()" in line or ".append(" in line}
    assert len(row_lines) == 3
    tracemalloc.start()
    try:
        for _ in range(1000):
            with jax_compile.setup_phase("compose") as phase:
                assert phase._span is trace._NOOP
        current, peak = tracemalloc.get_traced_memory()
        kept = tracemalloc.take_snapshot().filter_traces(
            [tracemalloc.Filter(True, jax_compile.__file__), tracemalloc.Filter(True, trace.__file__)]
        )
    finally:
        tracemalloc.stop()
    assert len(rows) == 1000
    assert {stat.traceback[0].lineno for stat in kept.statistics("lineno")} <= row_lines
    assert all(stat.traceback[0].filename == jax_compile.__file__ for stat in kept.statistics("lineno"))
    assert peak - current <= 2048


# --------------------------------------------------------------------------- #
# ring semantics
# --------------------------------------------------------------------------- #


def test_ring_wraparound_keeps_newest_and_counts_drops():
    t = trace.configure(plane="train", capacity=4, trace_id="ringtest")
    for i in range(10):
        trace.instant(f"ev{i}")
    assert [ev[trace._EV_NAME] for ev in t.events()] == ["ev6", "ev7", "ev8", "ev9"]
    s = t.stats()
    assert s["Telemetry/spans_recorded"] == 10
    assert s["Telemetry/spans_dropped"] == 6
    assert s["Telemetry/ring_size"] == 4
    assert s["Telemetry/ring_capacity"] == 4


def test_span_nesting_records_parent_ids():
    t = trace.configure(plane="train", trace_id="nesttest")
    with trace.span("outer") as outer:
        assert trace.current_span_id() == outer.span_id
        with trace.span("inner") as inner:
            assert inner.span_id != outer.span_id
    evs = {ev[trace._EV_NAME]: ev for ev in t.events()}
    assert evs["inner"][trace._EV_PARENT] == outer.span_id
    assert evs["outer"][trace._EV_PARENT] == ""
    assert evs["outer"][trace._EV_DUR] >= evs["inner"][trace._EV_DUR]


def test_add_span_cross_thread_parenting_with_preallocated_id():
    """The serve request-lifecycle shape: the parent id is allocated at admit,
    the queue-wait child records (from another thread) BEFORE the parent."""
    t = trace.configure(plane="serve", trace_id="xthread")
    parent_id = trace.new_span_id()
    t0 = time.monotonic()
    done = threading.Event()

    def batcher_thread():
        trace.add_span("serve/queue_wait", t0, t0 + 0.01, parent_id=parent_id)
        done.set()

    threading.Thread(target=batcher_thread).start()
    assert done.wait(5.0)
    trace.add_span("serve/request", t0, t0 + 0.02, span_id=parent_id, status="ok")
    evs = {ev[trace._EV_NAME]: ev for ev in t.events()}
    assert evs["serve/queue_wait"][trace._EV_PARENT] == parent_id
    assert evs["serve/request"][trace._EV_SID] == parent_id
    assert evs["serve/request"][trace._EV_ARGS] == {"status": "ok"}


def test_span_records_exception_and_still_propagates():
    t = trace.configure(trace_id="exctest")
    with pytest.raises(ValueError, match="boom"):
        with trace.span("train/update"):
            raise ValueError("boom")
    (ev,) = t.events()
    assert ev[trace._EV_ARGS]["error"] == "ValueError: boom"


# --------------------------------------------------------------------------- #
# Chrome-trace / Perfetto schema
# --------------------------------------------------------------------------- #


def test_chrome_trace_schema(tmp_path):
    trace.configure(plane="serve", trace_id="cafe0123", capacity=64)
    with trace.span("serve/infer", batch=3):
        trace.instant("failpoint/reload.canary", action="raise")
    path = trace.export(str(tmp_path / "telemetry" / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    assert set(doc) == {"traceEvents", "displayTimeUnit", "metadata"}
    assert doc["displayTimeUnit"] == "ms"
    assert doc["metadata"]["trace_id"] == "cafe0123"
    assert doc["metadata"]["plane"] == "serve"
    meta, *events = doc["traceEvents"]
    assert meta["ph"] == "M" and meta["name"] == "process_name"
    assert meta["args"]["name"] == "sheeprl-serve"
    by_name = {e["name"]: e for e in events}
    x = by_name["serve/infer"]
    assert x["ph"] == "X" and x["cat"] == "serve"
    assert isinstance(x["ts"], float) and isinstance(x["dur"], float) and x["dur"] >= 0
    # wall-anchored microseconds: the ts must be ~now, not a raw perf_counter
    assert abs(x["ts"] / 1e6 - time.time()) < 300
    assert x["args"]["trace_id"] == "cafe0123" and x["args"]["batch"] == 3
    i = by_name["failpoint/reload.canary"]
    assert i["ph"] == "i" and i["s"] == "t" and i["args"]["action"] == "raise"
    # the instant nests under the enclosing span
    assert i["args"]["parent_id"] == x["args"]["span_id"]


def test_configure_mirrors_env_and_children_join_the_parents_trace():
    t = trace.configure(plane="orchestrate", capacity=32, trace_id="abcd1234")
    spec = os.environ[trace.ENV_VAR]
    assert "plane=orchestrate" in spec and "trace_id=abcd1234" in spec
    # what a spawned child would do at import time
    child = trace.configure_from_env({trace.ENV_VAR: spec})
    assert child.trace_id == t.trace_id == "abcd1234"
    assert child.plane == "orchestrate" and child.capacity == 32
    trace.disable()
    assert trace.ENV_VAR not in os.environ
    assert trace.configure_from_env({}) is None
    assert trace.configure_from_env({trace.ENV_VAR: "1"}).plane == "train"


# --------------------------------------------------------------------------- #
# trace-id propagation into the run's other record surfaces
# --------------------------------------------------------------------------- #


def test_trace_id_stamped_into_health_events(tmp_path):
    from sheeprl_tpu.core.health import append_event

    trace.configure(trace_id="deadbeef")
    append_event(str(tmp_path), "serve_reload_rollback", 7, path="x.ckpt")
    trace.disable()
    append_event(str(tmp_path), "divergence_detected", 9)
    rows = [json.loads(ln) for ln in (tmp_path / "events.jsonl").read_text().splitlines()]
    assert rows[0]["event"] == "serve_reload_rollback" and rows[0]["step"] == 7
    assert rows[0]["trace_id"] == "deadbeef" and rows[0]["path"] == "x.ckpt"
    assert "trace_id" not in rows[1]  # disabled: no empty-string noise


def test_trace_id_stamped_into_failpoint_hits_and_instants():
    trace.configure(trace_id="feedface")
    failpoints.configure("p:fire")
    assert failpoints.failpoint("p") is True
    assert failpoints.counts()["p"] == {"hits": 1, "fires": 1, "last_trace_id": "feedface"}
    names = [ev[trace._EV_NAME] for ev in trace.get_tracer().events()]
    assert "failpoint/p" in names


def test_trace_id_stamped_into_certified_sidecars(tmp_path):
    from sheeprl_tpu.utils.checkpoint import certified_sidecar, certify

    ckpt = str(tmp_path / "ckpt_10.safetensors")
    trace.configure(trace_id="0ddball0")
    certify(ckpt, crc32=123, size=456, policy_step=10)
    with open(certified_sidecar(ckpt)) as f:
        payload = json.load(f)
    assert payload["trace_id"] == "0ddball0" and payload["policy_step"] == 10
    trace.disable()
    certify(ckpt, crc32=123, size=456)
    with open(certified_sidecar(ckpt)) as f:
        assert "trace_id" not in json.load(f)


# --------------------------------------------------------------------------- #
# metrics fabric: registry + exposition
# --------------------------------------------------------------------------- #


def test_registry_merges_providers_and_isolates_crashes():
    tel_registry.register("good", lambda: {"Serve/ok": 3})
    tel_registry.register("bad", lambda: 1 / 0)
    snap = tel_registry.collect()
    assert snap["Serve/ok"] == 3
    assert snap["Telemetry/provider_errors"] == 1
    tel_registry.unregister("bad")
    assert "Telemetry/provider_errors" not in tel_registry.collect()
    assert tel_registry.providers() == ("good",)


def test_default_providers_cover_compile_trace_and_device():
    tel_registry.register_default_providers()
    assert set(tel_registry.providers()) >= {"compile", "device", "trace"}
    snap = tel_registry.collect()
    assert snap["Telemetry/enabled"] == 0  # tracer disabled by the fixture
    assert isinstance(snap["Compile/retraces"], (int, float))
    assert snap["Device/count"] >= 1


def test_prometheus_exposition_names_types_and_run_info():
    trace.configure(trace_id="beef0001")
    text = tel_export.to_prometheus(
        {"Serve/latency_p50_ms": 1.5, "Compile/retraces": 0, "Serve/source": "a-string"},
        extra_labels={"plane": "serve"},
    )
    lines = text.splitlines()
    assert 'sheeprl_run_info{plane="serve",trace_id="beef0001"} 1' in lines
    assert "# TYPE sheeprl_serve_latency_p50_ms gauge" in lines
    assert "sheeprl_serve_latency_p50_ms 1.5" in lines
    assert "sheeprl_compile_retraces 0" in lines
    assert not any("a-string" in ln for ln in lines)  # strings are not series
    assert tel_export.sanitize_name("Serve/latency+p50 ms") == "sheeprl_serve_latency_p50_ms"


def test_jsonl_sink_appends_snapshot_rows(tmp_path):
    tel_registry.register("x", lambda: {"Serve/ok": 1})
    trace.configure(trace_id="51deca5e")
    sink = tel_export.JsonlSink(str(tmp_path / "metrics.jsonl"), interval_s=3600)
    sink.flush()
    sink.stop()  # final flush; thread never started, stop() must still work
    rows = [json.loads(ln) for ln in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert len(rows) == 2 and sink.lines_written == 2
    assert rows[0]["metrics"]["Serve/ok"] == 1
    assert rows[0]["trace_id"] == "51deca5e"


# --------------------------------------------------------------------------- #
# device introspection + MFU arithmetic
# --------------------------------------------------------------------------- #


def test_chip_peak_table_and_mfu_arithmetic():
    import types

    v5e = types.SimpleNamespace(device_kind="TPU v5e")
    assert tel_device.chip_peak_flops(v5e) == 197e12
    assert tel_device.mfu(197e12, 1.0, v5e) == pytest.approx(1.0)
    assert tel_device.mfu(98.5e12, 1.0, v5e) == pytest.approx(0.5)
    unknown = types.SimpleNamespace(device_kind="Quantum Abacus")
    assert tel_device.chip_peak_flops(unknown) is None
    assert tel_device.mfu(1e12, 1.0, unknown) is None  # never fabricate a peak
    assert tel_device.mfu(None, 1.0, v5e) is None
    assert tel_device.mfu(1e12, 0.0, v5e) is None


def test_hbm_gauges_report_device_count_on_cpu():
    gauges = tel_device.hbm_gauges()
    assert gauges["Device/count"] == 8.0  # conftest forces the 8-device mesh


def test_capture_window_single_slot_and_finally_safety(monkeypatch, tmp_path):
    started, stopped = [], []

    class _FakeProfiler:
        @staticmethod
        def start_trace(d):
            started.append(d)

        @staticmethod
        def stop_trace():
            stopped.append(True)

    import jax

    monkeypatch.setattr(jax, "profiler", _FakeProfiler)
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    assert tel_device.start_capture(d1) is True
    assert tel_device.capture_active()
    assert tel_device.start_capture(d2) is False  # one trace per process
    assert tel_device.toggle_capture(d1) == "stopped"
    assert not tel_device.capture_active()
    with pytest.raises(RuntimeError, match="mid-window"):
        with tel_device.CaptureWindow(d2):
            raise RuntimeError("mid-window")
    assert started == [d1, d2] and len(stopped) == 2  # __exit__ closed the window
    assert tel_device.stop_capture() is None  # idempotent when idle


def test_guarded_fn_captures_cost_analysis_flops():
    import jax.numpy as jnp

    from sheeprl_tpu.core import compile as jax_compile

    gfn = jax_compile.guarded_jit(lambda x: (x * 2.0 + 1.0).sum(), name="telemetry_test.flops")
    spec = jax_compile.spec_like(jnp.ones((128, 128), jnp.float32))
    gfn.aot_compile(spec)
    stats = gfn.stats()
    assert "step_flops" in stats and "flops_dispatched" in stats
    assert jax_compile.step_flops("telemetry_test.flops") == gfn.last_step_flops
    if gfn.last_step_flops is not None:  # cost_analysis is backend-dependent
        assert gfn.last_step_flops > 0
        gfn(jnp.ones((128, 128), jnp.float32))
        assert gfn.flops_dispatched == pytest.approx(gfn.last_step_flops)


# --------------------------------------------------------------------------- #
# serve stats: bounded latency reservoir + window gauges (the small fix)
# --------------------------------------------------------------------------- #


def test_serve_stats_latency_reservoir_is_bounded():
    from sheeprl_tpu.serve.stats import ServeStats

    stats = ServeStats(latency_window=8)
    for ms in range(100):  # old observations must be evicted, not accumulated
        stats.observe_latency(ms / 1000.0)
    snap = stats.snapshot()
    assert snap["Serve/latency_window_size"] == 8
    assert snap["Serve/latency_window_cap"] == 8
    # percentiles cover ONLY the last 8 observations (92..99 ms)
    assert snap["Serve/latency_p50_ms"] == pytest.approx(96.0)
    assert snap["Serve/latency_p99_ms"] == pytest.approx(99.0)


def test_serve_stats_snapshot_resort_only_when_dirty():
    from sheeprl_tpu.serve.stats import ServeStats

    stats = ServeStats(latency_window=4)
    stats.observe_latency(0.002)
    stats.observe_latency(0.001)
    first = stats.snapshot()
    assert first["Serve/latency_p50_ms"] == pytest.approx(2.0)
    assert not stats._lat_dirty
    cached = stats._lat_sorted
    assert stats.snapshot()["Serve/latency_p50_ms"] == pytest.approx(2.0)
    assert stats._lat_sorted is cached  # idle stats polling re-uses the sort
    stats.observe_latency(0.005)
    assert stats.snapshot()["Serve/latency_window_size"] == 3


# --------------------------------------------------------------------------- #
# the accelerator guarantee: span recording adds NO host<->device traffic
# --------------------------------------------------------------------------- #


@pytest.mark.timeout(300)
def test_span_recording_adds_no_host_transfers_to_a_warm_fused_iteration():
    """A warm fused PPO iteration wrapped in spans (the exact seams ppo.py
    uses) runs under ``jax.transfer_guard("disallow")`` with the tracer
    RECORDING: span timestamps/ids are pure host work, so instrumentation must
    introduce zero implicit pulls or uploads."""
    import gymnasium as gym
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import make_update_impl
    from sheeprl_tpu.config import instantiate, load_config
    from sheeprl_tpu.core.runtime import build_runtime
    from sheeprl_tpu.envs import ingraph as ig
    from sheeprl_tpu.utils.optim import with_clipping
    from sheeprl_tpu.utils.utils import PlayerParamsSync

    n_envs, t_steps = 16, 8
    n_data = n_envs * t_steps
    cfg = load_config(
        overrides=[
            "exp=ppo",
            "env=jax_cartpole",
            f"env.num_envs={n_envs}",
            f"algo.rollout_steps={t_steps}",
            f"algo.per_rank_batch_size={n_data}",
            "algo.update_epochs=1",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
            "seed=7",
        ]
    )
    runtime = build_runtime(cfg.fabric)
    venv = ig.make_vector_env(cfg, n_envs, 7, device=runtime.device)
    space = venv.single_action_space
    assert isinstance(space, gym.spaces.Discrete)
    agent, params, player = build_agent(
        runtime, (int(space.n),), False, cfg, venv.single_observation_space, None
    )
    player.params = jax.device_put(player.params, runtime.device)
    venv.reset(seed=7)
    collector = ig.InGraphRolloutCollector(
        venv, player, rollout_steps=t_steps, gamma=float(cfg.algo.gamma), name="tel_zt"
    )
    tx = with_clipping(instantiate(dict(cfg.algo.optimizer))(), cfg.algo.max_grad_norm)
    opt_state = tx.init(params)
    update_impl = make_update_impl(
        agent, tx, cfg, runtime, n_data, ["state"], [], PlayerParamsSync(player.params)
    )
    trainer = ig.FusedInGraphTrainer(collector, update_impl, n_extras=3, name="tel_zt")
    extras = (jnp.float32(cfg.algo.clip_coef), jnp.float32(cfg.algo.ent_coef), jnp.float32(1.0))
    k0, k1, k2 = (k for k in jax.random.split(jax.random.PRNGKey(5), 3))

    params, opt_state, flat, _r, _t = trainer.step(params, opt_state, k0, *extras)
    jax.block_until_ready(flat)

    tracer = trace.configure(plane="train", trace_id="zerotraffic")
    with jax.transfer_guard("disallow"):
        for i, k in enumerate((k1, k2)):
            with trace.span("train/update", fused=True, iter=i):
                params, opt_state, flat, _r, _t = trainer.step(params, opt_state, k, *extras)
            trace.instant("train/iter_done", iter=i)
        jax.block_until_ready(flat)  # fence only — not a transfer
        with pytest.raises(Exception):
            jnp.add(flat, 1.0)  # implicit host->device upload: guard is live
    names = [ev[trace._EV_NAME] for ev in tracer.events()]
    assert names.count("train/update") == 2 and names.count("train/iter_done") == 2
    # besides the loop's own four, each guarded call records its execute span (and no route: nothing was AOT-warmed)
    assert sorted(set(names) - {"train/update", "train/iter_done"}) == ["tel_zt.ingraph_train.execute"]
    assert tracer.stats()["Telemetry/spans_recorded"] == 6
    venv.close()


# --------------------------------------------------------------------------- #
# spans follow a profiler capture (no tracer configured)
# --------------------------------------------------------------------------- #


@pytest.fixture
def follow_jax_captures():
    import jax

    trace.follow_captures(jax.profiler.TraceAnnotation)
    yield jax
    trace.follow_captures(None)


def _capture_span_names(trace_dir):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return [
        ev.name
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith(trace.ANNOTATION_PREFIX)
    ]


def test_spans_are_recorded_and_annotated_only_while_a_profiler_session_is_open(follow_jax_captures, tmp_path):
    jax = follow_jax_captures
    with trace.span("before"):
        pass
    assert trace.get_tracer() is None and not trace.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("train.call", step=3) as outer:
            assert trace.current_span_id() == outer.span_id
            with trace.span("train.fence"):
                pass
        worker = threading.Thread(target=lambda: trace.span("prefetch.sample", parent_id=outer.span_id).__enter__().__exit__(None, None, None))
        worker.start()
        worker.join(10)
        assert not worker.is_alive()
        trace.add_span("added", 0.0, 1.0)  # after the fact: the ring only
    finally:
        jax.profiler.stop_trace()
    with trace.span("after"):
        pass
    assert not trace.enabled()  # following a capture configures no tracer: no trace id, no export
    ring = trace.get_tracer().events()
    assert [ev[trace._EV_NAME] for ev in ring] == ["train.fence", "train.call", "prefetch.sample", "added"]
    by_name = {ev[trace._EV_NAME]: ev for ev in ring}
    assert by_name["train.fence"][trace._EV_PARENT] == by_name["train.call"][trace._EV_SID]
    assert by_name["prefetch.sample"][trace._EV_PARENT] == by_name["train.call"][trace._EV_SID]
    assert by_name["train.call"][trace._EV_ARGS] == {"step": 3}
    assert sorted(_capture_span_names(str(tmp_path))) == [
        "sheeprl.prefetch.sample", "sheeprl.train.call", "sheeprl.train.fence",
    ]
    # the next capture starts a ring of its own
    second = tmp_path / "second"
    jax.profiler.start_trace(str(second))
    try:
        with trace.span("again"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [ev[trace._EV_NAME] for ev in trace.get_tracer().events()] == ["again"]


def test_no_session_and_no_tracer_never_reaches_the_recording_layer(follow_jax_captures, monkeypatch):
    def boom(*a, **k):
        raise AssertionError("a span was recorded with no tracer configured and no profiler session open")

    for name in ("_begin", "_record_instant", "_record_span", "_capture_ring"):
        monkeypatch.setattr(trace, name, boom)
    assert trace.span("train.call", step=1) is trace._NOOP
    assert trace.instant("x") is None and trace.add_span("y", 0.0, 1.0) is None
    assert trace.new_span_id() == "" and trace.current_span_id() == ""
    assert trace.get_tracer() is None


def test_a_configured_tracer_also_annotates_an_open_capture(follow_jax_captures, tmp_path):
    jax = follow_jax_captures
    t = trace.configure(plane="train", trace_id="both")
    with trace.span("outside"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("inside"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [ev[trace._EV_NAME] for ev in t.events()] == ["outside", "inside"]
    assert trace.get_tracer() is t
    assert _capture_span_names(str(tmp_path)) == ["sheeprl.inside"]


def test_prefetch_sample_hangs_under_the_get_that_launched_it():
    import numpy as np

    from sheeprl_tpu.data.prefetch import DevicePrefetcher

    t = trace.configure(plane="train", trace_id="prefetch")
    sampled = threading.Event()

    def sample_fn(n_samples, **_):
        sampled.set()
        return {"x": np.zeros((n_samples, 4), np.float32)}

    with DevicePrefetcher(sample_fn, device=None, chunk=2, chunk_key="n_samples") as pf:
        pf.get(n_samples=1)  # sync: sampled in line, launches the speculation of two
        pf.get(n_samples=1)  # speculated: waits for the worker's chunk, launches the next
        pf.get(n_samples=1)  # piece
    rows = t.events()
    gets = [ev for ev in rows if ev[trace._EV_NAME] == "prefetch.get"]
    assert [ev[trace._EV_ARGS]["served"] for ev in gets] == ["sync", "speculated", "piece"]
    samples = [ev for ev in rows if ev[trace._EV_NAME] == "prefetch.sample"]
    in_line = [ev for ev in samples if ev[trace._EV_TID] == gets[0][trace._EV_TID]]
    on_worker = [ev for ev in samples if ev[trace._EV_TID] != gets[0][trace._EV_TID]]
    assert [ev[trace._EV_PARENT] for ev in in_line] == [gets[0][trace._EV_SID]]
    assert in_line[0][trace._EV_ARGS] == {"n_samples": 1}
    # the worker's first job was launched by the first get, and carries its id across the thread
    assert on_worker and on_worker[0][trace._EV_PARENT] == gets[0][trace._EV_SID]
    assert on_worker[0][trace._EV_ARGS] == {"n_samples": 2}
    h2d = [ev for ev in rows if ev[trace._EV_NAME] == "prefetch.h2d"]
    assert len(h2d) == len(samples) and {ev[trace._EV_ARGS]["bytes"] for ev in h2d} == {16, 32}
    assert {ev[trace._EV_PARENT] for ev in h2d} <= {ev[trace._EV_SID] for ev in gets}


def test_player_push_says_why_it_moved_nothing():
    from types import SimpleNamespace

    from sheeprl_tpu.utils.utils import DreamerPlayerSync

    t = trace.configure(plane="train", trace_id="psync")
    params = {"world_model": {"encoder": 1}, "actor": 2}
    psync = DreamerPlayerSync(SimpleNamespace(player_on_host=False), params, wm_keys=("encoder",))
    player = SimpleNamespace()
    psync.push(player, params)
    assert player.wm_params is params["world_model"] and player.actor_params == 2
    (ev,) = t.events()
    assert ev[trace._EV_NAME] == "player.push" and ev[trace._EV_ARGS] == {"skipped": "rebind"}
