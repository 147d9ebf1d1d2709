"""Driver ``learner``: the learner path that ``<algo>.main()`` is built from.

Set-up makes the calls of ``dreamer_v3.main()`` lines 550-586 (and the same
wiring of ``dream_and_ponder.main()``) through the module the configuration
names: ``build_agent`` -> ``DreamerPlayerSync`` -> ``make_train_fn`` ->
``make_sequential_replay``, at the shipped ``fabric.player_on_host`` and
``algo.player_sync_every``. The window repeats what ``main()`` does per train
call (lines 906-926): ``prefetcher.get``, ``split(rng)``, ``train_fn``, the
fence ``main()`` makes while its timer is on (the shipped ``metric.log_level``
is 1, so it is on), ``psync.push``, and then waits until the player's copy is
ready, as ``main()``'s next env step does when it acts with it.

The first ``warmup_steps`` steps go through that same closure and are the steps
`check.py` compares with the plain reference once the window has closed.
"""

from __future__ import annotations

import importlib
import os
import queue
import shutil
import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np

SEED_MOD = 2147483629  # a prime under 2**31: every --seed maps to a valid PRNG seed


class Spans:
    """Host spans of the benchmark's own calls, kept in memory: (name, start, end)."""

    def __init__(self, annotate: bool):
        self.rows: List[tuple] = []
        self._annotate = annotate

    def __call__(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, spans: Spans, name: str):
        self._spans, self._name, self._ann = spans, name, None

    def __enter__(self):
        if self._spans._annotate:
            import jax

            self._ann = jax.profiler.TraceAnnotation(f"bench.{self._name}")
            self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        self._spans.rows.append((self._name, self._t0, time.perf_counter()))
        if self._ann is not None:
            self._ann.__exit__(*exc)


class Watcher(threading.Thread):
    """Stamps each step's completion by blocking on its returned ``counter``,
    in order, off the dispatching thread."""

    def __init__(self):
        super().__init__(name="chipbench-watcher", daemon=True)
        self.queue: "queue.Queue" = queue.Queue()
        self.done_at: List[float] = []
        self.error = None

    def run(self):
        while True:
            item = self.queue.get()
            if item is None:
                return
            try:
                item.block_until_ready()
            except Exception as e:  # a failed step is reported, not swallowed
                self.error = e
                return
            self.done_at.append(time.perf_counter())


def stage(name: str, t_start: float) -> None:
    """One line on standard error for each stage of set-up: seconds since the
    process started and the host memory held, so a slow or heavy stage shows."""
    with open("/proc/self/status") as f:
        rss = next((line.split()[1] for line in f if line.startswith("VmRSS")), "0")
    print(f"[setup] {time.perf_counter() - t_start:8.2f}s rss={int(rss) / 2**20:6.2f}GiB {name}", file=sys.stderr, flush=True)


def spaces_of(config: Dict[str, Any]):
    import gymnasium as gym

    obs = gym.spaces.Dict(
        {
            k: gym.spaces.Box(0, 255, tuple(v["shape"]), np.uint8)
            if v["dtype"] == "uint8"
            else gym.spaces.Box(-np.inf, np.inf, tuple(v["shape"]), np.dtype(v["dtype"]))
            for k, v in config["obs"].items()
        }
    )
    if config["actions"]["type"] != "discrete":
        raise ValueError("the learner driver's replay rows are one-hot discrete actions")
    return obs, (int(config["actions"]["n"]),), False


def build(cell: Dict[str, Any], seed: int, rehearse: bool, t_start: float = 0.0) -> Dict[str, Any]:
    """Everything the window drives, built once: the object `run` warms up is the
    one it times and the one `check.py` reads."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu import cli
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import PLAYER_WM_KEYS
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.core import compile as jax_compile
    from sheeprl_tpu.core.runtime import build_runtime
    from sheeprl_tpu.data.factory import make_sequential_replay
    from sheeprl_tpu.utils.utils import DreamerPlayerSync

    from common import load_module  # the benchmark's own loader

    config, traffic = cell["config_file"], cell["traffic_file"]
    seed32 = int(seed) % SEED_MOD
    overrides = list(config["overrides"]) + list(traffic["overrides"]) + [f"seed={seed32}"]
    sizes = dict(config["sizes"])
    if rehearse:
        overrides += list(config["rehearse_overrides"])
        sizes.update(config["rehearse_sizes"])
    stage("imports done", t_start)
    cfg = compose(config_name="config", overrides=overrides)
    cli._apply_global_flags(cfg)  # what cli.run_algorithm sets before main(): compile policy, timer, matmul precision
    runtime = build_runtime(cfg.fabric)
    algo = config["algo"]
    agent = importlib.import_module(f"sheeprl_tpu.algos.{algo}.agent")
    train_mod = importlib.import_module(f"sheeprl_tpu.algos.{algo}.{algo}")
    obs_space, actions_dim, is_continuous = spaces_of(config)
    obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)

    # weights from the seed, on the device in one jitted call, made by the
    # reference's layout: the program loads what it did not make
    reference = load_module("reference", config["reference"], cell["here"])
    ref_sizes = reference.sizes_from(sizes)
    spec = reference.param_spec(ref_sizes)
    make_weights = jax.jit(lambda s: reference.make_params(spec, s))
    weights = make_weights(jnp.int32(seed32))

    stage("weights made", t_start)
    modules, params, player = agent.build_agent(
        runtime, actions_dim, is_continuous, cfg, obs_space,
        weights["world_model"], weights["actor"], weights["critic"], weights["target_critic"],
    )
    stage("build_agent done", t_start)
    psync = DreamerPlayerSync(runtime, params, wm_keys=PLAYER_WM_KEYS, every=cfg.algo.get("player_sync_every", 1))
    init_opt, train_fn = train_mod.make_train_fn(modules, cfg, runtime, is_continuous, actions_dim, psync)
    state: Dict[str, Any] = {}
    built = {
        "cfg": cfg, "runtime": runtime, "player": player, "psync": psync, "train_fn": train_fn, "init_opt": init_opt,
        "state": state, "reference": reference, "ref_sizes": ref_sizes, "make_weights": make_weights,
    }
    reseed(built, seed, params)
    stage("state placed, player synced", t_start)
    rb, prefetcher = make_sequential_replay(cfg, runtime, None, obs_keys)
    rb.seed(seed32)  # main() leaves the sampler on OS entropy; a run is a function of --seed
    rows_mod = load_module("", "replay_rows", cell["here"])
    n_rows = int(cfg.buffer.size) // int(cfg.env.num_envs)
    rows = rows_mod.make_rows(seed32, n_rows, config, traffic)
    with prefetcher.guard():
        rb.add(rows, validate_args=cfg.buffer.validate_args)
    stage("replay filled", t_start)
    # main()'s AOT warm-up of the train step (lines 647-724): the window's calls
    # then dispatch the executable built from these specs
    from sheeprl_tpu.utils.utils import NUMPY_TO_JAX_DTYPE
    from jax.sharding import NamedSharding, PartitionSpec as P

    warmup = jax_compile.AOTWarmup(enabled=jax_compile.aot_enabled(cfg))
    if warmup.enabled:
        bsz = int(cfg.algo.per_rank_batch_size) * runtime.world_size
        batches_spec = {
            k: jax.ShapeDtypeStruct(
                (1, int(cfg.algo.per_rank_sequence_length), bsz, *v.shape[2:]),
                NUMPY_TO_JAX_DTYPE.get(np.dtype(v.dtype), jnp.float32),
                sharding=NamedSharding(runtime.mesh, P(None, None, "data")),
            )
            for k, v in rows.items()
        }
        warmup.add(
            train_fn,
            jax_compile.specs_of(state["params"]),
            jax_compile.specs_of(state["opt_states"]),
            jax_compile.specs_of(state["moments"]),
            jax_compile.spec_like(state["counter"]),
            batches_spec,
            jax_compile.spec_like(state["rng"]),
        )
        warmup.start()
        warmup.wait()
    stage("train step compiled or loaded", t_start)
    built.update(prefetcher=prefetcher, rb=rb, rows=rows, rows_mod=rows_mod)
    return built


def reseed(built: Dict[str, Any], seed: int, params=None) -> None:
    """The state as ``main()`` lays it out before its first train call, from
    ``seed``: weights (made anew unless given), optimizer states, moments,
    counter, key, the player's copy and, once it exists, the sampler's generator."""
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments

    seed32 = int(seed) % SEED_MOD
    runtime, state = built["runtime"], built["state"]
    if params is None:
        params = built["make_weights"](jnp.int32(seed32))
    state.clear()
    state.update(
        opt_states=runtime.place_params(built["init_opt"](params)),
        moments=init_moments(),
        counter=jnp.int32(0),
        rng=jax.random.PRNGKey(seed32),
    )
    state["params"] = runtime.place_params(params)
    built["psync"].push(built["player"], state["params"], force=True)
    built["seed32"] = seed32
    if "rb" in built:
        built["rb"].seed(seed32)


def make_step(built: Dict[str, Any], spans: Spans):
    """One train call as ``main()`` makes it. Returns the step's ``counter``
    (its completion handle), the batch it consumed, its key and its metrics."""
    import jax

    from sheeprl_tpu.utils.timer import timer

    cfg, st = built["cfg"], built["state"]
    prefetcher, train_fn, psync, player = built["prefetcher"], built["train_fn"], built["psync"], built["player"]
    batch_size = int(cfg.algo.per_rank_batch_size) * built["runtime"].world_size
    seq = int(cfg.algo.per_rank_sequence_length)

    def step():
        with spans("sample"):
            batches = prefetcher.get(batch_size=batch_size, sequence_length=seq, n_samples=1)
        with spans("dispatch"):
            st["rng"], key = jax.random.split(st["rng"])
            st["params"], st["opt_states"], st["moments"], st["counter"], flat, named = train_fn(
                st["params"], st["opt_states"], st["moments"], st["counter"], batches, key
            )
        if not timer.disabled:
            with spans("fence"):
                jax.block_until_ready(st["params"])
        with spans("player_sync"):
            psync.push(player, st["params"], flat=flat)
            # main()'s next env step acts with these parameters, so the transfer and the
            # host unravel are on its path; with no env in the window, wait for them here
            # (without a consumer the pulls queue up without bound: PERF.md, Findings)
            jax.block_until_ready((player.wm_params, player.actor_params))
        return st["counter"], batches, key, named

    return step


def run(cell, seed, seconds, trace, rehearse, devices, t_start, out_dir) -> Dict[str, Any]:
    import jax

    from sheeprl_tpu.core import compile as jax_compile

    from common import load_module

    check = load_module("", "check", cell["here"])
    built = build(cell, seed, rehearse, t_start)
    spans = Spans(annotate=trace)
    step = make_step(built, spans)

    # ---- warm-up: the compared steps, through the window's own call and feed
    probe = check.Probe(built)
    for i in range(int(cell["traffic_file"]["warmup_steps"])):
        counter, batches, key, named = step()
        jax.block_until_ready((built["state"]["params"], built["state"]["opt_states"]))
        probe.after_step(i, batches, key, named)
    probe.finish_setup()
    del batches, named
    stage("compared steps done", t_start)
    spans.rows.clear()
    stats0 = jax_compile.process_stats()
    trace_dir = os.path.join(out_dir, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the benchmark's own spans are enough; Python frames are 100x the events
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)

    # ---- the window
    watcher = Watcher()
    watcher.start()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    dispatched = 0
    if trace:  # a trace holds some 20,000 device events a step: a few seconds are enough, and all that can be read in time
        seconds = min(seconds, float(cell["traffic_file"]["trace_seconds"]))
    while time.perf_counter() - t0 < seconds:
        counter, _batches, _key, _named = step()
        watcher.queue.put(counter)
        dispatched += 1
    jax.block_until_ready(built["state"]["params"])
    watcher.queue.put(None)
    watcher.join(timeout=120)
    t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    if watcher.error is not None or watcher.is_alive():
        raise RuntimeError(f"a step of the window did not complete: {watcher.error!r}")

    stats1 = jax_compile.process_stats()
    # the window runs from t0 to the completion of the last step dispatched before
    # --seconds ran out: all the work over all the time, with no step cut in two
    done = [t - t0 for t in watcher.done_at]
    window_s = done[-1]
    intervals = np.diff(np.asarray([0.0] + done)) * 1e3
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    out: Dict[str, Any] = {
        "attempted": dispatched,
        "failed": dispatched - len(done),
        # the longest interval and where it fell: a run that lost seconds to one pause shows it here
        "steps": {"in_window": len(done), "window_s": window_s, "asked_s": seconds,
                  "longest_interval_ms": float(intervals.max()), "longest_at_step": int(intervals.argmax())},
        "end_to_end": {
            "gsteps_per_s": len(done) / window_s,
            "step_ms_p95": float(np.percentile(intervals, 95)),
            "setup_s": setup_s,
        },
        "memory_peak_bytes": memory_peak,
        "spans": [(n, a - t0, b - t0) for n, a, b in spans.rows],
        "window_s": window_s,
        "compile": {"at_window_start": stats0, "at_window_end": stats1},
        "config": cell["config_file"],
        "n_devices": len(devices),
    }
    if trace:
        reduce = load_module("", "reduce", cell["here"])
        out["trace"] = reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)  # traces are large: read, then gone

    # ---- the comparison, once the window has closed, the peak is read and the program's state is freed
    stage("window closed", t_start)
    built["prefetcher"].close()
    built["state"].clear()
    del step
    for k in ("rb", "player", "psync", "train_fn", "prefetcher"):
        built.pop(k)
    out["check"] = probe.compare(cell["config_file"])
    stage("compared with the reference", t_start)
    return out
