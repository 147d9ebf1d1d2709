"""Benchmark entrypoint for the driver: prints ONE JSON line.

One process for each chip. A target whose work runs in this process
(``ppo``, ``dv3``, ``all``, ``ingraph``, ``ingraph_train``, ``telemetry``)
initialises JAX here, must find an accelerator, and starts no child
that needs it. A target whose work runs in children (``compile``, ``serve``
on the session's backend; the declared CPU drills ``health``, ``orchestrate``,
``serve_fleet``, ``transport``, ``fsdp``, ``checkpoint``, ``population``)
keeps this process off the chip. A measurement path that finds no chip fails:
there is no CPU fallback, a failed phase makes the exit code non-zero, and
neither writes a ledger row. ``--smoke`` is the declared CPU self-test.

The ``all`` workloads, both on the real chip:

1. PPO env-steps/sec on CartPole-v1 (BASELINE.md target metric #1; headline
   ``value``). Reference anchor: 81.27 s for 65_536 steps on 4 CPUs => ~806
   env-steps/s (sheeprl v0.5.5 SB3 comparison table, README.md:99-115).
2. DreamerV3-S jitted train step at the Atari-100K shape (batch 16 x seq 64,
   64x64x3 pixels, bf16-mixed) — g-steps/s, replayed frames/s, and MFU
   (XLA-estimated FLOPs per step / elapsed / chip peak). Reference anchor:
   ~14 h for Atari-100K on an RTX 3080 (README.md:44-51) ≈ 1 g-step/s at
   replay_ratio 1 — reported as ``dv3_vs_baseline``.

Every record is also appended to the persistent cross-run ledger
(``benchmarks/ledger.jsonl`` or ``--ledger``/``$SHEEPRL_TPU_BENCH_LEDGER``),
and ``bench.py --check-regressions`` runs the regression sentinel over it:
the newest round's SPS/MFU/p99/peak-HBM metrics against the median of prior
same-status rounds with direction-aware per-metric thresholds, exiting 4 (and
emitting ``Regress/*`` rows) on a breach. See howto/observability.md.
"""

from __future__ import annotations

import contextlib
import json
import sys
import time

def _chip_peak_flops(device):
    # single source of truth for the per-chip bf16 peak table lives in the
    # telemetry fabric (imported lazily: `--check-regressions` never imports jax)
    from sheeprl_tpu.telemetry.device import chip_peak_flops

    return chip_peak_flops(device)


#: targets whose work runs IN THIS PROCESS on the session's backend; every other
#: target runs its work in children and keeps this process off the chip
_INPROC_TARGETS = ("ppo", "dv3", "all", "ingraph", "ingraph_train", "telemetry")


def _require_accelerator(platform: str, what: str) -> None:
    """A measurement path that finds no chip fails: no CPU fallback, no CPU
    number under a device metric's name (``--smoke`` is the CPU self-test)."""
    if platform == "cpu":
        import os

        raise SystemExit(
            f"bench.py: {what} ran on platform 'cpu' "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}): this target measures the "
            "accelerator and there is no CPU fallback; `--smoke` is the declared CPU self-test"
        )


def _provenance() -> dict:
    """run_id + git SHA + telemetry trace pointers stamped on every bench
    record, so a ledger row is attributable to the exact tree and trace
    that produced it (null-tolerant: a missing git binary or disabled tracer
    must never cost the measurement)."""
    import os
    import subprocess
    import uuid

    out = {"run_id": uuid.uuid4().hex[:12], "git_sha": None}
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True,
            text=True,
            timeout=10,
        )
        out["git_sha"] = sha.stdout.strip() or None
    except Exception:
        pass
    try:
        from sheeprl_tpu.telemetry import trace

        out["trace_id"] = trace.current_trace_id() or None
        out["trace_path"] = (
            trace.export(os.path.join("logs", "telemetry", f"bench_{out['run_id']}.trace.json"))
            if trace.enabled()
            else None
        )
    except Exception:
        out["trace_id"] = out["trace_path"] = None
    return out


def _ppo_pass(total_steps: int) -> float:
    from sheeprl_tpu.cli import run

    t0 = time.perf_counter()
    run(
        overrides=[
            "exp=ppo",
            f"algo.total_steps={total_steps}",
            "algo.rollout_steps=128",
            "algo.per_rank_batch_size=64",
            "env.num_envs=8",
            "env.sync_env=True",
            "env.capture_video=False",
            "algo.mlp_keys.encoder=[state]",
            "algo.run_test=False",
            "metric.log_level=0",
            "metric.disable_timer=True",
            "checkpoint.every=999999999",
            "checkpoint.save_last=False",
            "buffer.memmap=False",
        ]
    )
    return total_steps / (time.perf_counter() - t0)


def bench_ppo(total_steps: int = 65536, passes: int = 3) -> dict:
    """PPO throughput with variance control: one short warmup pass absorbs jit
    compilation, then ``passes`` full runs are timed and the MEDIAN reported
    with its spread.

    A single pass mixes cold compile into the rate, and every iteration pays
    one synchronous host<->device round trip for the on-policy params refresh,
    so the number needs a warm median over repeats to be comparable.
    """
    _ppo_pass(8192)  # warmup: compile the train/rollout jits outside the timed passes
    sps = sorted(_ppo_pass(total_steps) for _ in range(passes))
    median = sps[len(sps) // 2] if passes % 2 else 0.5 * (sps[passes // 2 - 1] + sps[passes // 2])
    baseline_sps = 65536 / 81.27  # reference PPO benchmark: 65536 steps / 81.27 s (README.md:99-115)
    return {
        "metric": "ppo_cartpole_env_steps_per_sec",
        "value": round(median, 2),
        "unit": "env-steps/s",
        "vs_baseline": round(median / baseline_sps, 3),
        "ppo_passes": [round(v, 2) for v in sps],
        "ppo_spread": round((sps[-1] - sps[0]) / 2.0, 2),
    }


_INGRAPH_COMMON = (
    "exp=ppo",
    "algo.mlp_keys.encoder=[state]",
    "algo.cnn_keys.encoder=[]",
    "algo.run_test=False",
    # timers must stay on (they carry the rollout-phase split) => log_level=1;
    # the episode prints that come with it are swallowed by the devnull
    # redirect in _instrumented_ppo_pass, and log_every is pushed out of reach
    "metric.log_level=1",
    "metric.log_every=1000000000",
    "metric.disable_timer=False",
    "env.capture_video=False",
    "checkpoint.every=999999999",
    "checkpoint.save_last=False",
    "buffer.memmap=False",
)


def _instrumented_ppo_pass(overrides, total_steps: int) -> dict:
    """One full PPO run returning wall-clock AND rollout-phase env-steps/s.

    The rollout-phase number comes from the loop's own ``Time/env_interaction_time``
    timer; the cli resets timers at every metric flush, so the reset is held
    open for the duration of the pass and the accumulated sum read afterwards.
    """
    import os

    from sheeprl_tpu.cli import run
    from sheeprl_tpu.utils.timer import timer

    saved_reset = timer.__dict__["reset"]
    saved_timers = timer.timers
    timer.reset = lambda: None  # accumulate across log flushes for this pass
    timer.timers = {}
    try:
        t0 = time.perf_counter()
        with open(os.devnull, "w") as devnull, contextlib.redirect_stdout(devnull):
            run(overrides=list(overrides))
        wall = time.perf_counter() - t0
        phase = timer.compute()
    finally:
        setattr(timer, "reset", saved_reset)
        timer.timers = saved_timers
    env_s = float(phase.get("Time/env_interaction_time") or 0.0)
    return {
        "wall_sps": total_steps / wall,
        "rollout_sps": (total_steps / env_s) if env_s > 0 else None,
    }


def _fused_collect_sps(num_envs: int, rollout_steps: int, iters: int = 8) -> float:
    """Sustained env-steps/s of the fused ``lax.scan`` collector alone, fenced.

    This exists because the train loop's ``Time/env_interaction_time`` timer
    cannot measure the in-graph backend: ``collector.collect()`` is an async
    dispatch, so the timer records microseconds of enqueue while the real work
    overlaps the train phase. Here the collector is driven standalone and each
    measurement is fenced with ``block_until_ready`` on the carry (every
    iteration consumes the previous carry, so fencing the last one fences the
    whole chain).
    """
    import jax

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.config import load_config
    from sheeprl_tpu.core.runtime import build_runtime
    from sheeprl_tpu.envs import ingraph as ig

    cfg = load_config(
        overrides=[
            "exp=ppo",
            "env=jax_cartpole",
            f"env.num_envs={num_envs}",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
        ]
    )
    runtime = build_runtime(cfg.fabric)
    venv = ig.make_vector_env(cfg, num_envs, 42, device=runtime.device)
    _, _, player = build_agent(runtime, (2,), False, cfg, venv.single_observation_space, None)
    player.params = jax.device_put(player.params, runtime.device)
    venv.reset(seed=42)
    collector = ig.InGraphRolloutCollector(
        venv, player, rollout_steps=rollout_steps, gamma=float(cfg.algo.gamma), name="bench"
    )
    collector.collect()  # compile + first rollout
    jax.block_until_ready(venv.carry.obs)
    t0 = time.perf_counter()
    for _ in range(iters):
        collector.collect()
    jax.block_until_ready(venv.carry.obs)
    return iters * rollout_steps * num_envs / (time.perf_counter() - t0)


def bench_ingraph(
    num_envs: int = 4096, rollout_steps: int = 128, iters: int = 8, host_steps: int = 16384
) -> dict:
    """In-graph vectorized backend (envs/ingraph/) vs the host gym path.

    Headline: sustained fused-collect env-steps/s (``policy.act ∘ env.step``
    under one ``lax.scan``, fenced — see :func:`_fused_collect_sps`), compared
    against the repo's standing host-path PPO baseline (the exact bench_ppo
    CartPole shape, full loop) as ``vs_baseline``. Context fields report the
    host run's rollout-phase split and a full ingraph training run's wall-clock
    env-steps/s; on the CPU fallback the latter is bounded by the shared train
    phase, not the collector.
    """
    host_over = list(_INGRAPH_COMMON) + [
        "algo.rollout_steps=128",
        "algo.per_rank_batch_size=64",
        "env.num_envs=8",
        "env.sync_env=True",
    ]
    _instrumented_ppo_pass(host_over + ["algo.total_steps=2048"], 2048)  # compile warmup
    host = _instrumented_ppo_pass(host_over + [f"algo.total_steps={host_steps}"], host_steps)

    steps_per_iter = num_envs * rollout_steps
    ingraph_over = list(_INGRAPH_COMMON) + [
        "env=jax_cartpole",
        f"env.num_envs={num_envs}",
        f"algo.rollout_steps={rollout_steps}",
        "algo.per_rank_batch_size=16384",
        "algo.update_epochs=1",
    ]
    # warmup pass seeds the persistent compile cache, so the timed pass's first
    # iteration replays executables instead of compiling them
    _instrumented_ppo_pass(ingraph_over + [f"algo.total_steps={steps_per_iter}"], steps_per_iter)
    total = steps_per_iter * iters
    ing = _instrumented_ppo_pass(ingraph_over + [f"algo.total_steps={total}"], total)

    collect_sps = _fused_collect_sps(num_envs, rollout_steps, iters=iters)
    host_full = host["wall_sps"]
    speedup = collect_sps / host_full
    return {
        "metric": "ingraph_env_steps_per_sec",
        "value": round(collect_sps, 2),
        "unit": "env-steps/s",
        "vs_baseline": round(speedup, 2),
        "ingraph_env_steps_per_sec": round(collect_sps, 2),
        "ingraph_vs_host_x": round(speedup, 2),
        "ingraph_host_full_loop_env_steps_per_sec": round(host_full, 2),
        "ingraph_host_rollout_phase_env_steps_per_sec": (
            round(host["rollout_sps"], 2) if host["rollout_sps"] else None
        ),
        "ingraph_train_loop_env_steps_per_sec": round(ing["wall_sps"], 2),
        "ingraph_num_envs": num_envs,
        "ingraph_rollout_steps": rollout_steps,
    }


def bench_ingraph_train(num_envs: int = 4096, rollout_steps: int = 128, iters: int = 4) -> dict:
    """Whole-iteration fused training (envs/ingraph/fused.py): rollout scan +
    GAE + update epochs in ONE donated-carry jitted program, driven standalone
    and fenced.

    Headline: aggregate env-steps/s of the fused iteration — env steps both
    collected AND trained on per wall-clock second. ``vs_baseline`` is the
    ratio against the same-session fused collect-only number (the PR-10
    ``--target ingraph`` headline): on a TPU slice, where the collect scan is
    dispatch/latency-bound, the update rides in the same program largely for
    free and the ratio approaches 1; on a CPU host the collect scan is already
    FLOP-bound, so the update's forward+backward over every collected row is
    pure added compute and the ratio reports exactly what the host pays for it.
    The update's wall-clock share per iteration is reported alongside. Design
    target on a v5e slice (howto/ingraph_envs.md): >= 1M aggregate env-steps/s.
    """
    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import make_update_impl
    from sheeprl_tpu.config import instantiate, load_config
    from sheeprl_tpu.core.runtime import build_runtime
    from sheeprl_tpu.envs import ingraph as ig
    from sheeprl_tpu.utils.optim import with_clipping
    from sheeprl_tpu.utils.utils import PlayerParamsSync

    n_data = num_envs * rollout_steps
    cfg = load_config(
        overrides=[
            "exp=ppo",
            "env=jax_cartpole",
            f"env.num_envs={num_envs}",
            f"algo.rollout_steps={rollout_steps}",
            f"algo.per_rank_batch_size={n_data}",
            "algo.update_epochs=1",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
        ]
    )
    runtime = build_runtime(cfg.fabric)
    venv = ig.make_vector_env(cfg, num_envs, 42, device=runtime.device)
    agent, params, player = build_agent(runtime, (2,), False, cfg, venv.single_observation_space, None)
    player.params = jax.device_put(player.params, runtime.device)
    venv.reset(seed=42)
    collector = ig.InGraphRolloutCollector(
        venv, player, rollout_steps=rollout_steps, gamma=float(cfg.algo.gamma), name="bench"
    )
    tx = with_clipping(instantiate(dict(cfg.algo.optimizer))(), cfg.algo.max_grad_norm)
    opt_state = tx.init(params)
    params_sync = PlayerParamsSync(player.params)
    update_impl = make_update_impl(
        agent, tx, cfg, runtime, n_data, list(cfg.algo.mlp_keys.encoder), [], params_sync
    )
    trainer = ig.FusedInGraphTrainer(collector, update_impl, n_extras=3, name="bench")
    key = jax.random.PRNGKey(0)
    extras = (jnp.float32(cfg.algo.clip_coef), jnp.float32(cfg.algo.ent_coef), jnp.float32(1.0))

    def fused_step():
        nonlocal params, opt_state, key
        key, sub = jax.random.split(key)
        params, opt_state, _flat, _roll, _train = trainer.step(params, opt_state, sub, *extras)

    # same-session collect-only reference: identical env batch, policy, and
    # carry chain, minus the update — the difference IS the update's wall-clock.
    # A SEPARATE collector instance: lax.scan's jaxpr cache is keyed on the
    # scan-body function object, so tracing split ``collect`` and the fused
    # ``iteration`` over one collector's shared ``one_step`` closure replays
    # the first trace's captured param tracers into the second
    # (UnexpectedTracerError). Production loops trace only one per process.
    ref_collector = ig.InGraphRolloutCollector(
        venv, player, rollout_steps=rollout_steps, gamma=float(cfg.algo.gamma), name="bench_ref"
    )
    ref_collector.collect()
    jax.block_until_ready(venv.carry.obs)
    t0 = time.perf_counter()
    for _ in range(iters):
        ref_collector.collect()
    jax.block_until_ready(venv.carry.obs)
    collect_iter_s = (time.perf_counter() - t0) / iters
    collect_sps = n_data / collect_iter_s

    fused_step()  # compile + first iteration
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    for _ in range(iters):
        fused_step()
    jax.block_until_ready(params)
    fused_iter_s = (time.perf_counter() - t0) / iters
    fused_sps = n_data / fused_iter_s

    return {
        "metric": "ingraph_fused_train_env_steps_per_sec",
        "value": round(fused_sps, 2),
        "unit": "env-steps/s",
        "vs_baseline": round(fused_sps / collect_sps, 3),
        "ingraph_fused_train_env_steps_per_sec": round(fused_sps, 2),
        "ingraph_fused_train_update_s_per_iter": round(max(fused_iter_s - collect_iter_s, 0.0), 4),
        "ingraph_fused_train_iter_s": round(fused_iter_s, 4),
        "ingraph_collect_only_env_steps_per_sec": round(collect_sps, 2),
        "ingraph_fused_train_num_envs": num_envs,
        "ingraph_fused_train_rollout_steps": rollout_steps,
        "ingraph_fused_train_tpu_slice_target_env_steps_per_sec": 1_000_000,
    }


def bench_telemetry(num_envs: int = 256, rollout_steps: int = 32, iters: int = 8, reps: int = 3) -> dict:
    """Span-tracer overhead on the fused PPO iteration, plus auto-computed MFU.

    Three interleaved variants of the same AOT-warmed fused loop: ``baseline``
    (no instrumentation calls at all), ``spans-off`` (the production span/
    instant seams present, tracer disabled — the zero-cost-when-disabled
    guarantee as a measured number), and ``spans-on`` (tracer recording into
    the ring). Interleaving reps A/B/C absorbs thermal/scheduler drift; the
    assertions use each variant's best-of (overhead is additive, so the
    fastest rep of each is the least-noise comparison):

    - spans-on must cost < 2% env-steps/s vs baseline,
    - spans-off must be indistinguishable from baseline (< 1%, i.e. 0 modulo
      measurement noise).

    MFU is computed, not hand-derived: the fused step's FLOPs come from
    ``lowered.compile().cost_analysis()`` captured by the retrace guard at
    AOT-warm time (core/compile.py), divided by measured iteration time and
    the chip's bf16 peak (telemetry/device.py) — null on chips with no peak
    table entry rather than fabricated.
    """
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import make_update_impl
    from sheeprl_tpu.config import instantiate, load_config
    from sheeprl_tpu.core.runtime import build_runtime
    from sheeprl_tpu.envs import ingraph as ig
    from sheeprl_tpu.telemetry import device as tel_device
    from sheeprl_tpu.telemetry import trace
    from sheeprl_tpu.utils.optim import with_clipping
    from sheeprl_tpu.utils.utils import PlayerParamsSync

    n_data = num_envs * rollout_steps
    cfg = load_config(
        overrides=[
            "exp=ppo",
            "env=jax_cartpole",
            f"env.num_envs={num_envs}",
            f"algo.rollout_steps={rollout_steps}",
            f"algo.per_rank_batch_size={n_data}",
            "algo.update_epochs=1",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
        ]
    )
    runtime = build_runtime(cfg.fabric)
    venv = ig.make_vector_env(cfg, num_envs, 42, device=runtime.device)
    agent, params, player = build_agent(runtime, (2,), False, cfg, venv.single_observation_space, None)
    player.params = jax.device_put(player.params, runtime.device)
    venv.reset(seed=42)
    collector = ig.InGraphRolloutCollector(
        venv, player, rollout_steps=rollout_steps, gamma=float(cfg.algo.gamma), name="bench_tel"
    )
    tx = with_clipping(instantiate(dict(cfg.algo.optimizer))(), cfg.algo.max_grad_norm)
    opt_state = tx.init(params)
    params_sync = PlayerParamsSync(player.params)
    update_impl = make_update_impl(
        agent, tx, cfg, runtime, n_data, list(cfg.algo.mlp_keys.encoder), [], params_sync
    )
    trainer = ig.FusedInGraphTrainer(collector, update_impl, n_extras=3, name="bench_tel")
    key = jax.random.PRNGKey(0)
    extras = (jnp.float32(cfg.algo.clip_coef), jnp.float32(cfg.algo.ent_coef), jnp.float32(1.0))
    st = {"params": params, "opt": opt_state, "key": key}

    def plain_step():
        st["key"], sub = jax.random.split(st["key"])
        st["params"], st["opt"], _flat, _roll, _train = trainer.step(st["params"], st["opt"], sub, *extras)

    def traced_step():
        # the production fused loop's per-iteration seams: one update span +
        # one instant (ppo.py wraps the fused step exactly like this)
        with trace.span("train/update", fused=True):
            plain_step()
        trace.instant("bench/iter")

    saved_env = os.environ.get(trace.ENV_VAR)
    trace.disable()
    # AOT-warm registers the executable AND captures its cost_analysis() FLOPs
    trainer.step_fn.aot_compile(
        *trainer.warmup_specs(st["params"], st["opt"], st["key"], *extras)
    )
    plain_step()  # first dispatch
    jax.block_until_ready(st["params"])

    def measure(step) -> float:
        t0 = time.perf_counter()
        for _ in range(iters):
            step()
        jax.block_until_ready(st["params"])
        return n_data * iters / (time.perf_counter() - t0)

    base, off, on = [], [], []
    try:
        for _ in range(reps):
            trace.disable()
            base.append(measure(plain_step))
            off.append(measure(traced_step))
            trace.configure(plane="train", capacity=65536)
            on.append(measure(traced_step))
        tel_stats = trace.stats()
        trace_path = trace.export(
            os.path.join(tempfile.mkdtemp(prefix="bench_telemetry_"), "trace.json")
        )
    finally:
        trace.disable()
        if saved_env is not None:
            os.environ[trace.ENV_VAR] = saved_env

    overhead_on = (max(base) / max(on) - 1.0) * 100.0
    overhead_off = (max(base) / max(off) - 1.0) * 100.0
    if overhead_on >= 2.0:
        raise RuntimeError(
            f"span tracer costs {overhead_on:.2f}% env-steps/s on the fused loop (budget: < 2%)"
        )
    if overhead_off >= 1.0:
        raise RuntimeError(
            f"DISABLED span seams cost {overhead_off:.2f}% env-steps/s (must be 0 within noise)"
        )
    step_flops = trainer.step_fn.last_step_flops
    iter_s = n_data / max(base)
    mfu = tel_device.mfu(step_flops, iter_s, runtime.device)
    med = lambda xs: sorted(xs)[len(xs) // 2]
    return {
        "telemetry_tracer_overhead_pct": round(overhead_on, 3),
        "telemetry_disabled_overhead_pct": round(overhead_off, 3),
        "telemetry_baseline_env_steps_per_sec": round(med(base), 2),
        "telemetry_spans_off_env_steps_per_sec": round(med(off), 2),
        "telemetry_spans_on_env_steps_per_sec": round(med(on), 2),
        "telemetry_spans_recorded": tel_stats.get("Telemetry/spans_recorded"),
        "telemetry_trace_export_path": trace_path,
        "telemetry_step_tflops": round(step_flops / 1e12, 4) if step_flops else None,
        "telemetry_mfu": round(mfu, 4) if mfu is not None else None,
        "telemetry_num_envs": num_envs,
        "telemetry_rollout_steps": rollout_steps,
        "telemetry_overhead_budget_pct": 2.0,
    }


def bench_dv3(
    batch: int = 128,
    seq: int = 64,
    iters: int = 20,
    extra_overrides=("algo.imagination_scan_unroll=15",),
    key_prefix: str = "dv3",
) -> dict:
    """Time the fused DreamerV3-S train step at the measured-best TPU config.

    Defaults follow a 2026-08 sweep through the plug-in path that is gone (its
    scripts too): batch 128 with the H=15 imagination scan fully unrolled read
    ~27.7% MFU there (XLA-estimated flops; the T=64 dynamic scan's flops are
    NOT trip-count-scaled by XLA cost analysis). No cell of the chip benchmark
    has judged ``imagination_scan_unroll=15`` (ROADMAP Design 2a); see
    benchmarks/DV3_MFU_NOTES.md. ``key_prefix`` lets a second call report the
    batch-16 Atari-100K recipe shape as ``dv3_recipe_*``."""
    import gymnasium as gym
    import jax
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_fn
    from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
    from sheeprl_tpu.config.loader import load_config
    from sheeprl_tpu.core.runtime import Runtime

    cfg = load_config(
        overrides=[
            "exp=dreamer_v3",
            "algo=dreamer_v3_S",
            "env=dummy",
            "fabric.precision=bf16-mixed",
            f"algo.per_rank_batch_size={batch}",
            f"algo.per_rank_sequence_length={seq}",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.cnn_keys.decoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "algo.mlp_keys.decoder=[]",
            *extra_overrides,
        ]
    )
    runtime = Runtime(accelerator="auto", devices=1, precision=cfg.fabric.precision)
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    actions_dim = (6,)  # Atari-like discrete head (MsPacman has 9; 6 is the classic set)
    modules, params, _player = build_agent(runtime, actions_dim, False, cfg, obs_space)
    init_opt, train_fn = make_train_fn(modules, cfg, runtime, False, actions_dim)
    opt_states = runtime.replicate(init_opt(params))
    params = runtime.replicate(params)
    moments = init_moments()
    counter = np.int32(0)

    rng = np.random.default_rng(0)
    g, t, b, a = 1, seq, batch, int(np.sum(actions_dim))
    batches = {
        "rgb": jax.device_put(rng.integers(0, 255, (g, t, b, 3, 64, 64), dtype=np.uint8)),
        "actions": jax.device_put(rng.random((g, t, b, a), dtype=np.float32)),
        "rewards": jax.device_put(rng.random((g, t, b, 1), dtype=np.float32)),
        "terminated": jax.device_put(np.zeros((g, t, b, 1), dtype=np.float32)),
        "truncated": jax.device_put(np.zeros((g, t, b, 1), dtype=np.float32)),
        "is_first": jax.device_put(np.zeros((g, t, b, 1), dtype=np.float32)),
    }
    key = jax.random.PRNGKey(0)

    # XLA's own FLOP estimate for one compiled train step (model FLOPs for MFU)
    step_flops = None
    try:
        compiled = train_fn.lower(params, opt_states, moments, counter, batches, key).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        step_flops = float(cost.get("flops", 0.0)) or None
    except Exception:
        pass  # cost analysis is backend-dependent; MFU reported as null if absent

    # warmup (first call compiles / loads the cache); the timing fences are real
    # host pulls (np.asarray of a device scalar)
    for _ in range(2):
        params, opt_states, moments, counter, _flat, _m = train_fn(params, opt_states, moments, counter, batches, key)
    np.asarray(counter)

    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_states, moments, counter, _flat, _m = train_fn(params, opt_states, moments, counter, batches, key)
    np.asarray(counter)  # counter is carried through every step: pulls the whole chain
    elapsed = time.perf_counter() - t0

    gsteps_per_sec = iters / elapsed
    sec_per_step = elapsed / iters
    peak = _chip_peak_flops(runtime.device)
    mfu = (step_flops / sec_per_step / peak) if (step_flops and peak) else None
    # hand-counted model FLOPs: XLA's cost_analysis counts scan bodies once
    # instead of x trip count (benchmarks/DV3_MFU_NOTES.md), so the analytic
    # figure is the honest numerator for MFU
    try:
        from benchmarks.analytic_flops import dv3_step_flops

        analytic_flops = dv3_step_flops(cfg, batch, seq, actions_dim)["total"]
    except Exception as e:  # pure-Python counter: a failure is a bug, make it visible
        print(f"analytic flop count failed: {type(e).__name__}: {e}", file=sys.stderr)
        analytic_flops = None
    mfu_analytic = (analytic_flops / sec_per_step / peak) if (analytic_flops and peak) else None
    return {
        f"{key_prefix}_gsteps_per_sec": round(gsteps_per_sec, 3),
        f"{key_prefix}_frames_per_sec": round(gsteps_per_sec * batch * seq, 1),
        f"{key_prefix}_step_tflops": round(step_flops / 1e12, 3) if step_flops else None,
        f"{key_prefix}_mfu": round(mfu, 4) if mfu is not None else None,
        f"{key_prefix}_step_tflops_analytic": round(analytic_flops / 1e12, 3) if analytic_flops else None,
        f"{key_prefix}_mfu_analytic": round(mfu_analytic, 4) if mfu_analytic is not None else None,
        f"{key_prefix}_device": getattr(runtime.device, "device_kind", str(runtime.device)),
        # reference anchor: ~1 g-step/s on RTX 3080 (Atari-100K in ~14h, README.md:44-51)
        f"{key_prefix}_vs_baseline": round(gsteps_per_sec / 1.0, 3),
    }


def bench_smoke(total_steps: int = 128) -> dict:
    """Tiny PPO pass on the CPU backend for BOTH buffer backends.

    Exists so the bench harness itself is exercised by the test suite (as a
    non-slow test) on hosts with no accelerator, where nobody would otherwise
    notice the harness bit-rotting. Runs on the dummy env, a 16-step rollout, and both
    ``buffer.backend=host`` and ``buffer.backend=device`` so the on-policy HBM
    rollout path is covered too; a third pass over async env workers engages the
    interaction pipeline (core/pipeline.py) and reports the env-step time hidden
    behind device/host work. Numbers are NOT comparable to the real bench.
    """
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.core.pipeline import process_overlap_totals

    result = {
        "metric": _target_metric("smoke"),
        "unit": "env-steps/s",
        "smoke": True,
    }
    common = [
        "exp=ppo",
        f"algo.total_steps={total_steps}",
        "algo.rollout_steps=16",
        "algo.per_rank_batch_size=8",
        "algo.update_epochs=1",
        "env=dummy",
        "env.num_envs=2",
        "env.capture_video=False",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
        "algo.run_test=False",
        "metric.log_level=0",
        "metric.disable_timer=True",
        "checkpoint.every=999999999",
        "checkpoint.save_last=False",
        "buffer.memmap=False",
        "fabric.devices=1",
    ]
    for backend in ("host", "device"):
        t0 = time.perf_counter()
        run(overrides=[*common, "env.sync_env=True", f"buffer.backend={backend}"])
        result[f"smoke_{backend}_env_steps_per_sec"] = round(
            total_steps / (time.perf_counter() - t0), 2
        )
    # async env workers: the pipelined pass (Time/sps_pipeline_overlap's source)
    overlap_s0, overlap_n0 = process_overlap_totals()
    t0 = time.perf_counter()
    run(overrides=[*common, "env.sync_env=False", "buffer.backend=host"])
    result["smoke_pipeline_env_steps_per_sec"] = round(total_steps / (time.perf_counter() - t0), 2)
    overlap_s, overlap_n = process_overlap_totals()
    result["smoke_pipeline_overlap_s"] = round(overlap_s - overlap_s0, 3)
    result["smoke_pipeline_overlap_steps"] = overlap_n - overlap_n0
    if overlap_s > overlap_s0:
        result["smoke_sps_pipeline_overlap"] = round(
            (overlap_n - overlap_n0) * 2 / (overlap_s - overlap_s0), 2
        )
    result["value"] = result["smoke_host_env_steps_per_sec"]
    return result


_COMPILE_CHILD = r"""
import contextlib, json, os, sys, time
t0 = time.perf_counter()
from sheeprl_tpu.cli import run
from sheeprl_tpu.core import compile as jax_compile

overrides = json.loads(os.environ["_SHEEPRL_BENCH_COMPILE_OVERRIDES"])
with contextlib.redirect_stdout(sys.stderr):
    run(overrides=overrides)
stats = jax_compile.process_stats()
train = jax_compile.find("ppo.train")
import jax
print("BENCH_COMPILE " + json.dumps({
    "platform": jax.devices()[0].platform,
    "wall_s": round(time.perf_counter() - t0, 3),
    "first_train_step_s": round(train.first_call_s, 3) if train and train.first_call_s else None,
    "cache_hits": stats["cache_hits"],
    "cache_misses": stats["cache_misses"],
    "compile_seconds": round(stats["compile_seconds"], 3),
    "retraces": stats["retraces"],
}), flush=True)
"""


def bench_compile(total_steps: int = 64) -> dict:
    """Cold-vs-warm persistent-cache wall clock + time-to-first-train-step.

    Runs the same tiny PPO workload twice in FRESH subprocesses against one
    on-disk compilation cache at a fixed path under the checkout, emptied
    first: the cold child populates it, the warm child replays it. The parent
    never initialises a backend (the children need the chip). Subprocesses are the only honest measurement — in-process
    repeats would hit jit's in-memory trace cache and time nothing. The child
    reports ``first_train_step_s`` from the retrace guard's own first-call
    clock (core/compile.py GuardedFn.first_call_s), i.e. process start ->
    first fused train step returning, the latency the AOT warmup + persistent
    cache exist to shrink.
    """
    import json as _json
    import os
    import shutil
    import subprocess

    overrides = [
        "exp=ppo",
        f"algo.total_steps={total_steps}",
        "algo.rollout_steps=16",
        "algo.per_rank_batch_size=8",
        "algo.update_epochs=1",
        "env=dummy",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
        "algo.run_test=False",
        "metric.log_level=0",
        "metric.disable_timer=True",
        "checkpoint.every=999999999",
        "checkpoint.save_last=False",
        "buffer.memmap=False",
        "fabric.devices=1",
    ]
    result = {}
    here = os.path.dirname(os.path.abspath(__file__))
    cache_dir = os.path.join(here, ".jax_cache.bench_compile")
    shutil.rmtree(cache_dir, ignore_errors=True)  # the cold child must start from nothing
    try:
        env = dict(
            os.environ,
            JAX_COMPILATION_CACHE_DIR=cache_dir,
            SHEEPRL_TPU_COMP_CACHE_MIN_SECS="0",
            _SHEEPRL_BENCH_COMPILE_OVERRIDES=_json.dumps(overrides),
        )
        for phase in ("cold", "warm"):
            proc = subprocess.run(
                [sys.executable, "-c", _COMPILE_CHILD], env=env, cwd=here, capture_output=True, text=True,
                timeout=1200,
            )
            line = next((ln for ln in proc.stdout.splitlines() if ln.startswith("BENCH_COMPILE ")), None)
            if proc.returncode != 0 or line is None:
                raise RuntimeError(
                    f"compile bench {phase} child failed (rc={proc.returncode}): "
                    f"{(proc.stderr or proc.stdout)[-500:]}"
                )
            child = _json.loads(line[len("BENCH_COMPILE "):])
            _require_accelerator(child["platform"], f"the compile bench's {phase} child")
            result["compile_platform"] = child["platform"]
            result[f"compile_{phase}_wall_s"] = child["wall_s"]
            result[f"compile_{phase}_first_train_step_s"] = child["first_train_step_s"]
            result[f"compile_{phase}_cache_hits"] = child["cache_hits"]
            result[f"compile_{phase}_cache_misses"] = child["cache_misses"]
            result[f"compile_{phase}_compile_seconds"] = child["compile_seconds"]
            result[f"compile_{phase}_retraces"] = child["retraces"]
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    if result.get("compile_cold_wall_s") and result.get("compile_warm_wall_s"):
        result["compile_warm_speedup"] = round(
            result["compile_cold_wall_s"] / result["compile_warm_wall_s"], 3
        )
    return result


def bench_health() -> dict:
    """Self-healing runtime drill: detection latency + rollback wall clock.

    Reuses the scripts/health_smoke.py scenario (chaos reward-spike PPO run:
    the sentinel must detect the divergence, climb warn -> backoff -> rollback,
    restore a certified checkpoint, and complete). The numbers measure the
    health machinery itself — the smoke child runs on the CPU backend, so they
    are comparable across rounds but say nothing about accelerator throughput.
    """
    import importlib.util
    import os
    import tempfile

    spec = importlib.util.spec_from_file_location(
        "health_smoke",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts", "health_smoke.py"),
    )
    health_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(health_smoke)

    t0 = time.perf_counter()
    smoke = health_smoke.main(tempfile.mkdtemp(prefix="bench_health_"))
    return {
        "health_detection_latency_s": smoke["detection_latency_s"],
        "health_detection_latency_steps": smoke["detection_latency_steps"],
        "health_rollback_wall_s": smoke["rollback_wall_s"],
        "health_rollbacks": smoke["rollbacks"],
        "health_certified_sidecars": smoke["certified_sidecars"],
        "health_drill_wall_s": round(time.perf_counter() - t0, 3),
    }


def bench_orchestrate() -> dict:
    """Elastic-population drill: preemption-recovery latency + resow wall clock.

    Reuses the scripts/population_smoke.py fleet chaos drill (two PPO trials on
    two preemptible slots: controller kill-and-restart, two injected slot
    preemptions, one ChaosEnv divergence resown from the clean peer's certified
    checkpoint). Recovery latency is SIGTERM-exit to respawn of the resumed
    incarnation; resow wall is divergence verdict to the resown spawn. Both
    measure the orchestration machinery on the CPU backend — comparable across
    rounds, silent about accelerator throughput.
    """
    import importlib.util
    import os
    import tempfile

    spec = importlib.util.spec_from_file_location(
        "population_smoke",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts", "population_smoke.py"),
    )
    population_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(population_smoke)

    t0 = time.perf_counter()
    smoke = population_smoke.main(tempfile.mkdtemp(prefix="bench_orchestrate_"))
    return {
        "orchestrate_preempt_recovery_s": smoke["preempt_recovery_latency_s"],
        "orchestrate_preempt_recoveries": smoke["preempt_recovery_latencies_s"],
        "orchestrate_resow_wall_s": smoke["resow_wall_s"],
        "orchestrate_injections": smoke["injections"],
        "orchestrate_controller_incarnations": smoke["controller_incarnations"],
        "orchestrate_drill_wall_s": round(time.perf_counter() - t0, 3),
    }


def bench_transport(iters: int = 60, chunk_bytes: int = 8192) -> dict:
    """Host control-plane drill: collective latency + chunk-stream throughput.

    Runs a KVServer with two ControlPlane peers in-process (threads, real
    sockets — the same path scripts/transport_smoke.py drills across
    processes) and measures broadcast/barrier round-trips, the epoch-fenced
    chunk stream clean, and the SAME stream again under a 10% deterministic
    drop failpoint (``control.chunk_send:drop:prob=0.1;seed=7``) so the
    retry/resend overhead is a number, not a hope. CPU-backend machinery
    numbers — comparable across rounds, silent about the accelerator.
    """
    import threading

    from sheeprl_tpu.core import failpoints
    from sheeprl_tpu.parallel.control import ControlPlane, KVServer, SocketKV

    server = KVServer()
    server.start()
    try:
        p0 = ControlPlane(SocketKV(server.address), rank=0, world=2, scope="bench", timeout_ms=60_000)
        p1 = ControlPlane(SocketKV(server.address), rank=1, world=2, scope="bench", timeout_ms=60_000)
        payload = b"x" * chunk_bytes

        def timed_pair(fn0, fn1, n):
            samples = []

            def side(fn):
                fn()

            for _ in range(n):
                t0 = time.perf_counter()
                t = threading.Thread(target=side, args=(fn1,))
                t.start()
                fn0()
                t.join()
                samples.append((time.perf_counter() - t0) * 1000.0)
            samples.sort()
            return samples[len(samples) // 2]

        bcast_ms = timed_pair(
            lambda: p0.broadcast_str("b", "v"), lambda: p1.broadcast_str("b"), iters
        )
        barrier_ms = timed_pair(lambda: p0.barrier("t"), lambda: p1.barrier("t"), iters)

        def stream(channel, spec=None):
            p0.begin_session(channel)
            p1.adopt_epoch(channel)
            resends0 = p0.counters["Resilience/chunk_resends"]

            def send():
                if spec:
                    with failpoints.active(spec):
                        for i in range(iters):
                            p0.send_chunk(channel, i, payload)
                else:
                    for i in range(iters):
                        p0.send_chunk(channel, i, payload)

            t = threading.Thread(target=send)
            t0 = time.perf_counter()
            t.start()
            for i in range(iters):
                p1.recv_chunk(channel, i)
            t.join()
            wall = time.perf_counter() - t0
            return wall, p0.counters["Resilience/chunk_resends"] - resends0

        clean_wall, clean_resends = stream("clean")
        drop_wall, drop_resends = stream("drop", "control.chunk_send:drop:prob=0.1;seed=7")
        return {
            "transport_broadcast_p50_ms": round(bcast_ms, 3),
            "transport_barrier_p50_ms": round(barrier_ms, 3),
            "transport_chunk_roundtrip_ms": round(clean_wall / iters * 1000.0, 3),
            "transport_chunk_mb_per_s": round(iters * chunk_bytes / clean_wall / 1e6, 3),
            "transport_clean_resends": clean_resends,
            "transport_drop_resends": drop_resends,
            "transport_drop_overhead_x": round(drop_wall / clean_wall, 3),
            "transport_chunk_bytes": chunk_bytes,
            "transport_iters": iters,
        }
    finally:
        server.stop()


def _serve_level(addr, obs: dict, qps: float, duration_s: float) -> dict:
    """One open-loop load level: send at the offered rate WITHOUT waiting for
    responses (a closed-loop client would never overrun the server, hiding the
    backpressure behavior the sweep exists to show), collect latencies on a
    reader thread, report percentiles + terminal-status mix."""
    import json as _json
    import socket
    import threading

    sent: dict = {}
    latencies: list = []
    statuses: dict = {}
    lock = threading.Lock()
    sock = socket.create_connection(addr, timeout=10.0)
    rw = sock.makefile("rwb")

    def reader():
        while True:
            try:
                line = rw.readline()
            except (OSError, ValueError):
                return
            if not line:
                return
            resp = _json.loads(line)
            t1 = time.monotonic()
            with lock:
                t0 = sent.pop(resp.get("id"), None)
                statuses[resp["status"]] = statuses.get(resp["status"], 0) + 1
                if resp.get("status") == "ok" and t0 is not None:
                    latencies.append((t1 - t0) * 1000.0)

    rt = threading.Thread(target=reader, daemon=True)
    rt.start()
    n = max(1, int(qps * duration_s))
    interval = 1.0 / qps
    t_start = time.monotonic()
    for i in range(n):
        target_t = t_start + i * interval
        now = time.monotonic()
        if target_t > now:
            time.sleep(target_t - now)
        rid = f"q{qps}-{i}"
        with lock:
            sent[rid] = time.monotonic()
        rw.write((_json.dumps({"id": rid, "obs": obs}) + "\n").encode())
        rw.flush()
    send_elapsed = time.monotonic() - t_start
    settle_until = time.monotonic() + 10.0
    while time.monotonic() < settle_until:
        with lock:
            if not sent:
                break
        time.sleep(0.02)
    with lock:
        unresolved = len(sent)
    sock.close()
    rt.join(timeout=2.0)
    latencies.sort()
    pct = lambda p: round(latencies[min(len(latencies) - 1, int(len(latencies) * p))], 3) if latencies else None
    return {
        "offered_qps": qps,
        "achieved_qps": round(n / send_elapsed, 1),
        "sent": n,
        "ok": statuses.get("ok", 0),
        "rejected": statuses.get("rejected", 0),
        "shed": statuses.get("shed", 0),
        "deadline_missed": statuses.get("deadline_expired", 0),
        "errors": statuses.get("error", 0),
        "unresolved": unresolved,
        "p50_ms": pct(0.50),
        "p99_ms": pct(0.99),
    }


def bench_serve(qps_levels=(25, 50, 100, 200), duration_s: float = 3.0) -> dict:
    """Policy-serving QPS sweep: offered load vs p50/p99 latency.

    Reuses the scripts/serve_smoke.py fixture (tiny certified PPO checkpoint,
    subprocess server) and drives an open-loop generator at each offered QPS
    level. The sweep's invariant — asserted, not just reported — is ZERO
    retraces after warmup: every request mix lands on an AOT bucket. Headline
    ``serve_p99_ms`` is the p99 at the highest offered level. The server is a
    child on the session's backend: the parent stays off the chip (the fixture
    is built on its CPU backend) and the target fails when the policy step ran
    on a CPU (``fabric.player_on_host`` decides where it runs).
    """
    import importlib.util
    import os
    import signal
    import subprocess
    import tempfile

    spec = importlib.util.spec_from_file_location(
        "serve_smoke",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "scripts", "serve_smoke.py"),
    )
    serve_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve_smoke)

    t0 = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="bench_serve_")
    fixture = serve_smoke.build_fixture(workdir)
    ready_file = os.path.join(workdir, "ready.json")
    stats_file = os.path.join(workdir, "stats.json")
    log_file = os.path.join(workdir, "server.log")
    # the policy step on the accelerator is what this target measures; the
    # shipped default (fabric.player_on_host=True) serves from the host CPU
    proc = serve_smoke.launch_server(
        fixture, ready_file, stats_file, log_file, extra=("fabric.player_on_host=False",)
    )
    result: dict = {}
    try:
        info = serve_smoke.wait_ready(ready_file, proc, log_file, timeout=240.0)
        _require_accelerator(info["policy_platform"], "the serve bench's policy step")
        result["serve_policy_device"] = info["policy_device"]
        addr = (info["host"], info["port"])
        levels = [_serve_level(addr, fixture["obs"], qps, duration_s) for qps in qps_levels]
        stats = serve_smoke.rpc(addr, {"op": "stats"})
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=90)
    finally:
        if proc.poll() is None:
            proc.kill()
    retraces = stats.get("Compile/retraces")
    if retraces != 0:
        raise RuntimeError(f"{retraces} steady-state retraces during the QPS sweep (must be 0)")
    result["serve_levels"] = levels
    result["serve_retraces"] = retraces
    result["serve_aot_compiles"] = stats.get("Compile/aot_compiles")
    result["serve_batch_occupancy"] = stats.get("Serve/batch_occupancy")
    top = levels[-1]
    result["serve_p50_ms"] = top["p50_ms"]
    result["serve_p99_ms"] = top["p99_ms"]
    result["serve_offered_qps"] = top["offered_qps"]
    result["serve_sweep_wall_s"] = round(time.perf_counter() - t0, 3)
    return result


def bench_serve_fleet(
    qps_levels=(25, 50, 100), duration_s: float = 3.0, slo_p99_ms: float = 750.0
) -> dict:
    """Fleet availability sweep: offered-QPS levels THROUGH the failover
    router while the fleet is being abused — one replica SIGKILLed before the
    second level, a rolling certified deploy landing across the later levels —
    with an asserted p99 SLO and zero client-visible errors/losses at every
    level. This is the serving plane's availability number: what a client pays
    in tail latency for a crash plus a weight rollout, instead of an outage.

    Reuses scripts/serve_fleet_smoke.py's launcher (3 real serve replicas +
    supervisor subprocess). Headline ``serve_fleet_p99_ms`` is the p99 of the
    final post-deploy level at the top offered rate; ``serve_fleet_worst_p99_ms``
    (what the SLO gates) is the worst p99 across ALL chaos levels.
    """
    import importlib.util
    import os
    import signal
    import tempfile

    spec = importlib.util.spec_from_file_location(
        "serve_fleet_smoke",
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts", "serve_fleet_smoke.py"
        ),
    )
    fleet_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleet_smoke)
    serve_smoke = fleet_smoke.serve_smoke

    t0 = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="bench_fleet_")
    fixture = serve_smoke.build_fixture(workdir)
    fleet_dir = os.path.join(workdir, "fleet")
    ready_file = os.path.join(workdir, "router_ready.json")
    stats_file = os.path.join(workdir, "fleet_stats.json")
    log_file = os.path.join(workdir, "fleet.log")
    proc = fleet_smoke.launch_fleet(fixture, fleet_dir, ready_file, stats_file, log_file)
    result: dict = {}
    levels = []
    try:
        info = serve_smoke.wait_ready(ready_file, proc, log_file, timeout=600.0)
        addr = (info["host"], info["port"])

        def fleet_stats():
            return serve_smoke.rpc(addr, {"op": "stats"})

        levels.append(dict(_serve_level(addr, fixture["obs"], qps_levels[0], duration_s), chaos="baseline"))
        # chaos 1: SIGKILL one replica, then offer the next level while the
        # router fails over and the supervisor respawns the slot
        members = fleet_smoke.read_membership(os.path.join(fleet_dir, "membership.json"))
        os.kill(int(members[-1]["pid"]), signal.SIGKILL)
        for qps in qps_levels[1:]:
            levels.append(dict(_serve_level(addr, fixture["obs"], qps, duration_s), chaos="post_kill"))
        # chaos 2: certify a new generation and hold the top offered rate
        # while the rolling deploy drains/reboots replicas one at a time
        serve_smoke.write_generation(
            fixture["ckpt_dir"], serve_smoke.perturb(fixture["state"]), 200
        )
        deadline = time.monotonic() + 600.0
        while fleet_stats().get("Fleet/deploys", 0) < 1:
            if time.monotonic() > deadline:
                raise RuntimeError("rolling deploy never landed during the fleet sweep")
            levels.append(
                dict(_serve_level(addr, fixture["obs"], qps_levels[-1], duration_s), chaos="during_deploy")
            )
        levels.append(
            dict(_serve_level(addr, fixture["obs"], qps_levels[-1], duration_s), chaos="post_deploy")
        )
        stats = fleet_stats()
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
    # SLO gate — asserted, not just reported: chaos may cost tail latency and
    # sheds, never errors, losses, or an SLO breach
    worst_p99 = max(lv["p99_ms"] for lv in levels if lv["p99_ms"] is not None)
    for lv in levels:
        if lv["errors"] or lv["unresolved"]:
            raise RuntimeError(
                f"fleet sweep level {lv['chaos']}@{lv['offered_qps']}qps saw "
                f"{lv['errors']} errors / {lv['unresolved']} unresolved (must be 0)"
            )
    if worst_p99 > slo_p99_ms:
        raise RuntimeError(
            f"fleet sweep p99 {worst_p99:.1f} ms breached the {slo_p99_ms:.0f} ms SLO"
        )
    if stats.get("Fleet/replica_restarts", 0) < 1:
        raise RuntimeError("the SIGKILLed replica was never respawned during the sweep")
    top = levels[-1]
    result["serve_fleet_levels"] = levels
    result["serve_fleet_p50_ms"] = top["p50_ms"]
    result["serve_fleet_p99_ms"] = top["p99_ms"]
    result["serve_fleet_worst_p99_ms"] = round(worst_p99, 3)
    result["serve_fleet_slo_p99_ms"] = slo_p99_ms
    result["serve_fleet_qps"] = top["achieved_qps"]
    result["serve_fleet_restarts"] = stats.get("Fleet/replica_restarts")
    result["serve_fleet_deploys"] = stats.get("Fleet/deploys")
    result["serve_fleet_failovers"] = stats.get("Fleet/failovers")
    result["serve_fleet_fenced_writes"] = stats.get("Fleet/fenced_writes")
    result["serve_fleet_members"] = stats.get("Fleet/members")
    result["serve_fleet_sweep_wall_s"] = round(time.perf_counter() - t0, 3)
    return result


def _fsdp_child_main(iters: int = 5) -> dict:
    """The in-process body of ``bench.py --target fsdp`` (see :func:`bench_fsdp`).

    Runs inside a subprocess pinned to an 8-device virtual CPU mesh
    (``--xla_force_host_platform_device_count=8`` must be in XLA_FLAGS before
    jax initializes — which is why the parent cannot run this inline). Three
    arms over the same tiny MLP regression step:

    - **handoff**: ``parallel/handoff.shard_put`` byte accounting for a
      rollout-shaped payload vs the replicated ``device_put`` path — the
      headline ``fsdp_handoff_bytes_per_iter`` and the strict
      ``sharded < replicated`` acceptance gate.
    - **ddp vs fsdp**: jitted donated-carry train step with replicated vs
      parameter-sharded (``Runtime.place_params``) state — step time and
      device-0 param+opt footprint.
    - **overlap**: the same update inside the portable ``shard_map`` shim with
      ``overlap.accumulate_grads`` at 1 vs 4 microbatches (per-bucket psum) —
      the gradient-sync overlap arm. All programs compile through
      ``guarded_jit`` so the pinned program ledger records their collective
      op counts/bytes (the HLO auditor's rows come back in the result).
    """
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sheeprl_tpu.core import compile as jax_compile
    from sheeprl_tpu.core.runtime import Runtime
    from sheeprl_tpu.data.device_buffer import _shard_map
    from sheeprl_tpu.parallel import handoff, overlap
    from sheeprl_tpu.telemetry import programs as tel_programs

    out: dict = {"fsdp_devices": jax.device_count(), "fsdp_backend": jax.default_backend()}
    out["fsdp_xla_profile_applied"] = overlap.apply_xla_profile("overlap")

    # ---- tiny MLP regression step (shared by every arm)
    D, H, B = 256, 512, 512
    rng = np.random.default_rng(0)
    # master copies stay HOST numpy: on the CPU backend device_put aliases a
    # same-process jax buffer zero-copy, so a donated placed copy would delete
    # the master under the next arm's feet
    params = {
        "w1": (rng.standard_normal((D, H)) * 0.02).astype(np.float32),
        "b1": np.zeros((H,), np.float32),
        "w2": (rng.standard_normal((H, H)) * 0.02).astype(np.float32),
        "b2": np.zeros((H,), np.float32),
        "w3": (rng.standard_normal((H, D)) * 0.02).astype(np.float32),
        "b3": np.zeros((D,), np.float32),
    }
    tx = optax.adam(1e-3)
    batch = {
        "x": rng.standard_normal((B, D)).astype(np.float32),
        "y": rng.standard_normal((B, D)).astype(np.float32),
    }

    def loss_fn(p, b):
        h = jax.nn.relu(b["x"] @ p["w1"] + p["b1"])
        h = jax.nn.relu(h @ p["w2"] + p["b2"])
        pred = h @ p["w3"] + p["b3"]
        return jnp.mean(jnp.square(pred - b["y"])), ()

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    # ---- arm 1: per-shard handoff bytes on a rollout-shaped payload
    T, E = 16, 64
    payload = {
        "obs": rng.standard_normal((T, E, 128)).astype(np.float32),
        "actions": rng.standard_normal((T, E, 6)).astype(np.float32),
        "values": rng.standard_normal((T, E, 1)).astype(np.float32),
        "rewards": rng.standard_normal((T, E, 1)).astype(np.float32),
        "dones": np.zeros((T, E, 1), np.float32),
    }
    rt = Runtime(accelerator="cpu", devices=8, strategy="auto", precision="32-true")
    handoff.reset_stats()
    sharded = handoff.shard_put(payload, rt.mesh, batch_axis=1)
    jax.block_until_ready(sharded)
    st = handoff.stats()
    replicated_bytes = handoff.replicated_put_bytes(payload, rt.mesh)
    out["fsdp_handoff_bytes_per_iter"] = int(st["put_bytes"])
    out["fsdp_handoff_puts_per_iter"] = int(st["puts"])
    out["fsdp_handoff_replicated_bytes_per_iter"] = int(replicated_bytes)
    out["fsdp_handoff_reduction_x"] = round(replicated_bytes / max(st["put_bytes"], 1), 2)
    # acceptance gate: the sharded handoff must move STRICTLY fewer bytes than
    # the replicated path it replaces
    out["fsdp_handoff_gate_pass"] = bool(st["put_bytes"] < replicated_bytes)

    # ---- arm 2: ddp vs fsdp step time + device-0 param/opt footprint
    dev0 = rt.mesh.devices.ravel()[0]

    def _dev0_mb(tree) -> float:
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            if isinstance(leaf, jax.Array):
                for s in leaf.addressable_shards:
                    if s.device == dev0:
                        total += s.data.nbytes
        return round(total / 1e6, 3)

    def step(p, o, b):
        (loss, _), grads = grad_fn(p, b)
        updates, o = tx.update(grads, o, p)
        p = optax.apply_updates(p, updates)
        return p, o, loss

    def _fresh(tree):
        # defensive copy: the placed state is donated, and a zero-copy
        # device_put must never hand the master's memory to the donation
        return jax.tree_util.tree_map(np.array, tree)

    for strategy in ("auto", "fsdp"):
        srt = Runtime(accelerator="cpu", devices=8, strategy=strategy, precision="32-true")
        p = srt.place_params(_fresh(params))
        o = srt.place_params(tx.init(_fresh(params)))
        b = handoff.shard_put(batch, srt.mesh, batch_axis=0)
        label = "ddp" if strategy == "auto" else "fsdp"
        gfn = jax_compile.guarded_jit(step, name=f"bench.fsdp_step_{label}", donate_argnums=(0, 1))
        # AOT so the program lands in the pinned ledger with the HLO collective audit
        gfn.aot_compile(jax_compile.specs_of(p), jax_compile.specs_of(o), jax_compile.specs_of(b))
        p, o, loss = gfn(p, o, b)  # warm
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            p, o, loss = gfn(p, o, b)
        jax.block_until_ready(loss)
        out[f"fsdp_{label}_step_ms"] = round((time.perf_counter() - t0) / iters * 1e3, 3)
        out[f"fsdp_{label}_dev0_param_opt_mb"] = _dev0_mb((p, o))
    if out.get("fsdp_ddp_dev0_param_opt_mb"):
        out["fsdp_vs_ddp_mem_x"] = round(
            out["fsdp_ddp_dev0_param_opt_mb"] / max(out["fsdp_fsdp_dev0_param_opt_mb"], 1e-9), 2
        )

    # ---- arm 3: gradient-sync overlap (microbatched per-bucket psum) at
    # 1 vs 4 microbatches inside the portable shard_map shim
    mesh = rt.mesh
    for m in (1, 4):

        def overlap_body(p, o, b, _m=m):
            (loss, _), grads = overlap.accumulate_grads(
                grad_fn, p, b, microbatches=_m, axis_name="data", axis_size=8
            )
            updates, o = tx.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            return p, o, jax.lax.pmean(loss, "data")

        sm = _shard_map(
            overlap_body, mesh=mesh,
            in_specs=(P(), P(), P("data")), out_specs=(P(), P(), P()),
        )
        gfn = jax_compile.guarded_jit(sm, name=f"bench.fsdp_overlap_m{m}", donate_argnums=(0, 1))
        p = rt.place_params(_fresh(params))
        o = rt.place_params(tx.init(_fresh(params)))
        b = handoff.shard_put(batch, mesh, batch_axis=0)
        gfn.aot_compile(jax_compile.specs_of(p), jax_compile.specs_of(o), jax_compile.specs_of(b))
        p, o, loss = gfn(p, o, b)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            p, o, loss = gfn(p, o, b)
        jax.block_until_ready(loss)
        key = "fsdp_overlap_step_ms" if m == 4 else "fsdp_overlap_m1_step_ms"
        out[key] = round((time.perf_counter() - t0) / iters * 1e3, 3)

    # ---- HLO collective audit: every mesh program above landed in the pinned
    # program ledger (SHEEPRL_TPU_PROGRAMS, set by the parent) with the
    # auditor's collective dict — surface the per-program summary
    collective = {}
    for row in tel_programs.snapshot():
        col = row.get("collective")
        if col and row.get("name", "").startswith("bench.fsdp"):
            collective[row["name"]] = {
                "op_count": col.get("op_count"),
                "bytes": col.get("bytes"),
                "async_pairs": col.get("async_pairs"),
                "sync_ops": col.get("sync_ops"),
            }
    if collective:
        out["fsdp_collective"] = collective
        out["fsdp_collective_bytes_total"] = int(
            sum(c.get("bytes") or 0 for c in collective.values())
        )
    return out


def bench_fsdp(iters: int = 5, timeout_s: float = 600.0) -> dict:
    """DDP-vs-FSDP-vs-overlap step time + per-shard handoff bytes (ISSUE 18).

    Folds the retired ``scripts/fsdp_bench.py`` into the sentinel-gated bench:
    the measurement runs in a SUBPROCESS pinned to an 8-device virtual CPU
    mesh (``--xla_force_host_platform_device_count`` only takes effect before
    jax initializes) with a private compiled-program ledger, so the HLO
    collective auditor's rows come back with the timings. Headline:
    ``fsdp_handoff_bytes_per_iter`` (sentinel class ``handoff_bytes``,
    direction *lower*) — the bytes the donated per-shard rollout handoff
    actually moves, vs the replicated path's ``mesh_size x`` copy.
    """
    import os
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        xla = env.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in xla:
            env["XLA_FLAGS"] = (xla + " --xla_force_host_platform_device_count=8").strip()
        env["SHEEPRL_TPU_PROGRAMS"] = os.path.join(td, "programs.jsonl")
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env["_SHEEPRL_BENCH_FSDP_CHILD"] = str(int(iters))
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(repo, "bench.py")],
                env=env, capture_output=True, text=True, timeout=timeout_s,
            )
        except subprocess.TimeoutExpired:
            return {"fsdp_error": f"child exceeded {timeout_s}s"}
        for line in proc.stdout.splitlines():
            if line.startswith("FSDP_BENCH "):
                try:
                    return json.loads(line[len("FSDP_BENCH "):])
                except ValueError:
                    break
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-8:]
        return {"fsdp_error": f"child rc={proc.returncode}: " + " | ".join(tail)}


def _ckpt_child_main(reps: int = 3) -> dict:
    """Subprocess body of bench_checkpoint, pinned to the 8-device CPU mesh.

    Four timings on the SAME ~48 MiB mesh-sharded state:

    1. ``checkpoint_legacy_blocked_ms`` — the synchronous single-file
       ``save_state`` (the caller eats serialize + fsync);
    2. ``checkpoint_blocked_save_ms`` — the async sharded path's train-thread
       block (D2H snapshot only; serialize/fsync/commit ride the writer
       thread). The acceptance gate: strictly below legacy;
    3. ``checkpoint_commit_visible_ms`` — save() call to committed-and-
       discoverable (the window a preemption loses);
    4. ``checkpoint_elastic_restore_s`` / ``checkpoint_peer_restore_s`` —
       8-device save restored onto a 2-device mesh, and the peer-RAM fetch
       (control-plane chunk stream, zero storage reads) of the same payload.
    """
    import pickle
    import tempfile
    import threading

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import sheeprl_tpu.utils.ckpt_sharded as cs
    from sheeprl_tpu.utils.checkpoint import save_state

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("d",))
    rng = np.random.default_rng(0)
    state = {
        "params": {
            f"layer{i}": jax.device_put(
                rng.standard_normal((1024, 1536)).astype(np.float32),
                NamedSharding(mesh, PartitionSpec("d")),
            )
            for i in range(8)
        },
        "step": 1,
    }
    jax.block_until_ready(state["params"])
    state_bytes = sum(leaf.nbytes for leaf in state["params"].values())

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    out: dict = {"checkpoint_state_mb": round(state_bytes / 1e6, 1), "checkpoint_reps": reps}
    with tempfile.TemporaryDirectory() as td:
        legacy_ms = []
        for r in range(reps):
            t0 = time.perf_counter()
            save_state(os.path.join(td, f"legacy_{r}.ckpt"), state)
            legacy_ms.append((time.perf_counter() - t0) * 1e3)

        blocked_ms, visible_ms = [], []
        ck = cs.ShardedCheckpointer(process_index=0, world=1)
        try:
            last_path = None
            for r in range(reps):
                last_path = os.path.join(td, f"sharded_{r}.ckpt")
                t0 = time.perf_counter()
                pending = ck.save(last_path, state)
                blocked_ms.append(pending.blocked_s * 1e3)
                pending.wait(120.0)
                visible_ms.append((time.perf_counter() - t0) * 1e3)
                assert cs.is_committed(last_path)
        finally:
            ck.close()

        mesh_b = Mesh(np.array(devices[:2]), ("d",))
        t0 = time.perf_counter()
        restored = cs.elastic_restore(
            last_path,
            lambda key, shape, dtype: NamedSharding(mesh_b, PartitionSpec("d"))
            if key.startswith("/params/")
            else None,
        )
        jax.block_until_ready(restored["params"])
        out["checkpoint_elastic_restore_s"] = round(time.perf_counter() - t0, 3)

        # peer-RAM emergency path: two in-process control planes, real sockets
        from sheeprl_tpu.parallel.control import ControlPlane, KVServer, SocketKV

        payload = pickle.dumps(jax.device_get(state), protocol=pickle.HIGHEST_PROTOCOL)
        server = KVServer()
        server.start()
        try:
            p0 = ControlPlane(SocketKV(server.address), rank=0, world=2, scope="ckptbench", timeout_ms=60_000)
            p1 = ControlPlane(SocketKV(server.address), rank=1, world=2, scope="ckptbench", timeout_ms=60_000)
            p0.begin_session("ckpt_replicator")
            store = cs.PeerReplicaStore(p1, src_rank=0, poll_ms=20, fence_role="ckpt_replicator")
            store.start()
            push = threading.Thread(
                target=cs.replicate_to_peer, args=(p0, payload, 1), kwargs={"timeout_ms": 60_000}
            )
            push.start()
            push.join()
            # the restarted incarnation of rank 0 fetches its own snapshot back
            p0b = ControlPlane(SocketKV(server.address), rank=0, world=2, scope="ckptbench", timeout_ms=60_000)
            t0 = time.perf_counter()
            fetched = cs.fetch_from_peer(p0b, timeout_ms=60_000)
            assert fetched is not None and fetched[0] == 1
            pickle.loads(fetched[1])
            out["checkpoint_peer_restore_s"] = round(time.perf_counter() - t0, 3)
            store.stop()
            store.join(timeout=5.0)
        finally:
            server.stop()

    out["checkpoint_legacy_blocked_ms"] = round(median(legacy_ms), 3)
    out["checkpoint_blocked_save_ms"] = round(median(blocked_ms), 3)
    out["checkpoint_commit_visible_ms"] = round(median(visible_ms), 3)
    out["checkpoint_blocked_reduction_x"] = round(
        median(legacy_ms) / max(median(blocked_ms), 1e-6), 2
    )
    # acceptance gate: the async sharded path must block the train thread
    # STRICTLY less than the legacy synchronous save it replaces
    out["checkpoint_gate_pass"] = bool(median(blocked_ms) < median(legacy_ms))
    return out


def bench_checkpoint(reps: int = 3, timeout_s: float = 600.0) -> dict:
    """Sharded-checkpoint subsystem drill (elastic-checkpointing issue).

    Runs in a SUBPROCESS pinned to an 8-device virtual CPU mesh (the
    device-count flag only takes effect before jax initializes). Headline:
    ``checkpoint_blocked_save_ms`` (sentinel class ``blocked_save``, direction
    *lower*) — the milliseconds the training thread stalls per checkpoint,
    which the async writer reduces to the D2H snapshot alone."""
    import os
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        xla = env.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in xla:
            env["XLA_FLAGS"] = (xla + " --xla_force_host_platform_device_count=8").strip()
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env["TMPDIR"] = td
        env["_SHEEPRL_BENCH_CKPT_CHILD"] = str(int(reps))
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(repo, "bench.py")],
                env=env, capture_output=True, text=True, timeout=timeout_s,
            )
        except subprocess.TimeoutExpired:
            return {"checkpoint_error": f"child exceeded {timeout_s}s"}
        for line in proc.stdout.splitlines():
            if line.startswith("CKPT_BENCH "):
                try:
                    return json.loads(line[len("CKPT_BENCH "):])
                except ValueError:
                    break
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-8:]
        return {"checkpoint_error": f"child rc={proc.returncode}: " + " | ".join(tail)}


def bench_population(
    members: int = 8,
    envs_per_member: int = 8,
    epochs: int = 4,
    iters_per_epoch: int = 4,
    rollout_steps: int = 8,
    timeout_s: float = 600.0,
) -> dict:
    """Device-resident vmapped population vs the subprocess-per-trial fleet.

    Three subprocess children on the CPU backend, same training budget
    (``members x epochs x iters x rollout x envs`` env-steps):

    1. ``population.backend=fused`` on ONE device — the whole PBT population
       as one compiled vmapped program (orchestrate/fused_trainee.py); the
       headline ``population_agg_env_steps_per_sec`` is its aggregate
       training throughput, and ``population_fused_wall_s`` its wall clock
       including the single jax import + compile;
    2. the same fused program on a FORCED 8-device virtual mesh (member axis
       shard_map'd onto ``data``, one member's full train loop per device) —
       ``population_shard_scaling_x`` is its aggregate throughput over a
       1-member/1-device run's, the member-axis scaling factor (near-linear =
       approaching ``members``; the 8-member/1-device vmapped run is NOT the
       base because XLA already spreads its batched ops across the same
       physical cores);
    3. the classic subprocess backend: ``members`` independent trials on
       ``members`` slots through the real controller, each paying its own
       interpreter + jax import + compile — exactly the overhead the fused
       backend deletes. ``population_fused_speedup_x`` (wall/wall, sentinel
       class ``fused_speedup``) is the ISSUE 19 >=2x acceptance gate.
    """
    import os
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    steps_per_member = epochs * iters_per_epoch * rollout_steps * envs_per_member
    base_overrides = [
        "exp=ppo",
        "env=jax_cartpole",
        "metric.log_level=0",
        f"algo.rollout_steps={rollout_steps}",
        "algo.per_rank_batch_size=32",
        "algo.update_epochs=1",
        "seed=7",
    ]
    pop_spec = {
        "backend": "fused",
        "members": members,
        "envs_per_member": envs_per_member,
        "epochs": epochs,
        "iters_per_epoch": iters_per_epoch,
        "checkpoint_every": epochs,  # one certified slice set per run
        "domain_rand": True,
        "overrides": base_overrides,
    }

    def _child_env(devices: int = 1) -> dict:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("SHEEPRL_TPU_FAILPOINTS", None)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        if devices > 1:
            xla = env.get("XLA_FLAGS", "")
            if "--xla_force_host_platform_device_count" not in xla:
                env["XLA_FLAGS"] = (
                    xla + f" --xla_force_host_platform_device_count={devices}"
                ).strip()
        return env

    def _run_fused(td: str, tag: str, devices: int, n_members: int = None) -> dict:
        spec = dict(pop_spec, devices=devices)
        if n_members is not None:
            spec["members"] = n_members
        spec_path = os.path.join(td, f"{tag}.json")
        with open(spec_path, "w") as f:
            json.dump({"orchestrate": {"population": spec}}, f)
        t0 = time.perf_counter()
        proc = subprocess.run(
            [
                sys.executable, "-m", "sheeprl_tpu.orchestrate.fused_trainee",
                "--spec", spec_path, "--state-dir", os.path.join(td, tag),
            ],
            env=_child_env(devices), capture_output=True, text=True, timeout=timeout_s,
        )
        wall = time.perf_counter() - t0
        for line in proc.stdout.splitlines():
            if line.startswith("POPULATION_FUSED "):
                summary = json.loads(line[len("POPULATION_FUSED "):])
                summary["bench_wall_s"] = round(wall, 3)
                return summary
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-8:]
        raise RuntimeError(f"fused child ({tag}) rc={proc.returncode}: " + " | ".join(tail))

    def _run_subprocess_fleet(td: str) -> float:
        trial_overrides = base_overrides + [
            f"env.num_envs={envs_per_member}",
            "fabric.devices=1",
            f"algo.total_steps={steps_per_member}",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
            "algo.run_test=False",
            "buffer.memmap=False",
            f"checkpoint.every={steps_per_member // epochs}",
            "checkpoint.save_last=False",
        ]
        spec = {
            "orchestrate": {
                "slots": members,  # maximum parallelism: the baseline's best case
                "poll_interval_s": 0.2,
                "resow": {"enabled": False},
                "exploit": {"interval_s": 0.0},
            },
            "trials": [
                {
                    "key": f"t{i:02d}",
                    "overrides": trial_overrides + [f"seed={7 + i}"],
                    "hyperparams": {"algo.optimizer.lr": 1e-3},
                }
                for i in range(members)
            ],
        }
        spec_path = os.path.join(td, "subprocess_fleet.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        t0 = time.perf_counter()
        proc = subprocess.run(
            [
                sys.executable, "-m", "sheeprl_tpu.orchestrate.controller",
                "--spec", spec_path, "--state-dir", os.path.join(td, "subprocess_fleet"),
            ],
            env=_child_env(), capture_output=True, text=True, timeout=timeout_s,
        )
        wall = time.perf_counter() - t0
        result_line = next(
            (l for l in reversed(proc.stdout.splitlines()) if l.startswith("ORCHESTRATE_RESULT ")),
            None,
        )
        if proc.returncode != 0 or result_line is None:
            tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-8:]
            raise RuntimeError(f"subprocess fleet rc={proc.returncode}: " + " | ".join(tail))
        summary = json.loads(result_line.split("ORCHESTRATE_RESULT ", 1)[1])
        if summary.get("status") != "done":
            raise RuntimeError(f"subprocess fleet did not finish: {summary}")
        return wall

    out: dict = {
        "population_members": members,
        "population_env_steps": members * steps_per_member,
    }
    with tempfile.TemporaryDirectory(prefix="bench_population_") as td:
        fused = _run_fused(td, "fused_1dev", devices=1)
        out["population_agg_env_steps_per_sec"] = fused["agg_env_steps_per_s"]
        out["population_fused_wall_s"] = fused["bench_wall_s"]
        out["population_fused_train_wall_s"] = fused["train_wall_s"]
        out["population_fused_retraces"] = fused["retraces"]
        out["population_fused_exploits"] = fused["exploits"]
        out["population_fused_swaps"] = fused["swaps"]
        try:
            single = _run_fused(td, "fused_m1", devices=1, n_members=1)
            out["population_single_member_env_steps_per_sec"] = single["agg_env_steps_per_s"]
            # the forced-8-device child is occasionally signal-killed on a
            # loaded shared host — one retry before giving up on the scaling
            # numbers (the headline is already banked above)
            for attempt in (0, 1):
                try:
                    mesh = _run_fused(td, f"fused_8dev_a{attempt}", devices=8)
                    break
                except (RuntimeError, subprocess.TimeoutExpired):
                    if attempt:
                        raise
            out["population_mesh_agg_env_steps_per_sec"] = mesh["agg_env_steps_per_s"]
            out["population_mesh_world_size"] = mesh["world_size"]
            out["population_shard_scaling_x"] = round(
                mesh["agg_env_steps_per_s"] / max(single["agg_env_steps_per_s"], 1e-9), 3
            )
        except Exception as e:  # mesh child failure must not cost the headline
            out["population_mesh_error"] = f"{type(e).__name__}: {e}"
        try:
            sub_wall = _run_subprocess_fleet(td)
            out["population_subprocess_wall_s"] = round(sub_wall, 3)
            out["population_fused_speedup_x"] = round(
                sub_wall / max(fused["bench_wall_s"], 1e-9), 3
            )
        except Exception as e:
            out["population_subprocess_error"] = f"{type(e).__name__}: {e}"
    return out


def _target_metric(target: str) -> str:
    """Headline metric name for a bench target — the watchdog's failure record
    must name the metric the selected target WOULD have produced, not hardcode
    the PPO one (advisor r5 finding: a dv3-only failure record claiming
    ``ppo_cartpole_env_steps_per_sec`` misfiles the regression history)."""
    return {
        "ppo": "ppo_cartpole_env_steps_per_sec",
        "dv3": "dv3_gsteps_per_sec",
        "compile": "compile_warm_first_train_step_s",
        "health": "health_detection_latency_s",
        "orchestrate": "orchestrate_preempt_recovery_s",
        "serve": "serve_p99_ms",
        "serve_fleet": "serve_fleet_p99_ms",
        "transport": "transport_chunk_roundtrip_ms",
        "ingraph": "ingraph_env_steps_per_sec",
        "ingraph_train": "ingraph_fused_train_env_steps_per_sec",
        "telemetry": "telemetry_tracer_overhead_pct",
        "fsdp": "fsdp_handoff_bytes_per_iter",
        "checkpoint": "checkpoint_blocked_save_ms",
        "population": "population_agg_env_steps_per_sec",
        "smoke": "ppo_smoke_env_steps_per_sec",
        "all": "ppo_cartpole_env_steps_per_sec",  # PPO stays the headline value
    }[target]


# ---------------------------------------------------------------------------
# Cross-run regression sentinel (persistent ledger + --check-regressions)
# ---------------------------------------------------------------------------

_LEDGER_ENV = "SHEEPRL_TPU_BENCH_LEDGER"

# Direction-aware sentinel classes: key-substring -> (direction, default
# threshold fraction vs the median of prior rounds). Throughput and MFU must
# not fall; latencies, peak HBM, and overhead must not grow. Thresholds are
# per-class because the metrics' noise floors differ by an order of magnitude
# (SPS medians are stable to ~10%; p99 latency on a shared host is not).
_SENTINEL_CLASSES = (
    ("_per_sec", "higher", 0.10),
    ("mfu", "higher", 0.10),
    # achieved fleet throughput under chaos: an open-loop generator on a shared
    # host undershoots its offered rate noisily, hence the loose floor
    ("_qps", "higher", 0.25),
    ("_p99_ms", "lower", 0.25),
    ("_p50_ms", "lower", 0.25),
    ("hbm_peak", "lower", 0.05),
    ("overhead_pct", "lower", 0.50),
    # per-shard handoff bytes are pure payload-shape arithmetic — growth means
    # a leaf fell off the sharded path back onto the replicated one
    ("handoff_bytes", "lower", 0.02),
    # train-thread checkpoint stall: a D2H memcpy on a shared CPU host is
    # noisy, but growth past the floor means work leaked back onto the caller
    ("blocked_save", "lower", 0.50),
    # fused-population wall-clock advantage over the subprocess fleet: both
    # sides run on a shared CPU host, so the floor is loose — but the >=2x
    # acceptance gate means even a 25% slip is worth flagging
    ("fused_speedup", "higher", 0.25),
)


def _ledger_path(override=None) -> str:
    import os

    return (
        override
        or os.environ.get(_LEDGER_ENV)
        or os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks", "ledger.jsonl")
    )


def _append_ledger(result: dict, path=None) -> None:
    """Append this round's record to the persistent cross-run ledger. Never
    raises — losing a history row must not cost the measurement or the
    one-JSON-line stdout contract."""
    import os

    from sheeprl_tpu.core import failpoints

    path = _ledger_path(path)
    try:
        if failpoints.failpoint("bench.ledger_append", path=path) is failpoints.DROPPED:
            return
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(result) + "\n")
    except Exception:
        pass


def _read_bench_ledger(path: str) -> list:
    rows = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if isinstance(row, dict):
                    rows.append(row)
    except OSError:
        pass
    return rows


def check_regressions(ledger: str, thresholds: dict | None = None) -> tuple:
    """The cross-run sentinel: compare the NEWEST ledger round's sentinel
    metrics (SPS/MFU/p99/peak-HBM classes above) against the median of every
    prior round that carries the same ``status`` (this harness only writes
    ``ok`` rows; a row that says anything else is never a baseline for one that
    says ``ok``). Returns ``(report, rc)`` where the report carries one
    ``Regress/<metric>`` row per checked metric and rc is 4 on any breach — the
    CI-gate contract."""
    import statistics

    thresholds = thresholds or {}
    rows = _read_bench_ledger(ledger)
    report = {
        "metric": "bench_regression_sentinel",
        "ledger": ledger,
        "rounds_total": len(rows),
        "checked": 0,
        "regressions": [],
        "status": "ok",
    }
    if len(rows) < 2:
        report["status"] = "skipped"
        report["skip_reason"] = f"need >= 2 ledger rounds to compare, have {len(rows)}"
        report["value"] = 0
        return report, 0
    current = rows[-1]
    status = current.get("status", "ok")
    prior = [r for r in rows[:-1] if r.get("status", "ok") == status]
    if not prior:
        report["status"] = "skipped"
        report["skip_reason"] = f"no prior rounds with status={status!r} to compare against"
        report["value"] = 0
        return report, 0
    report["rounds_prior"] = len(prior)
    report["current_run_id"] = current.get("run_id")
    for key in sorted(current):
        val = current[key]
        if isinstance(val, bool) or not isinstance(val, (int, float)):
            continue
        cls = next(((d, t) for sub, d, t in _SENTINEL_CLASSES if sub in key), None)
        if cls is None:
            continue
        direction, thr = cls
        thr = float(thresholds.get(key, thr))
        hist = [
            float(r[key])
            for r in prior
            if isinstance(r.get(key), (int, float)) and not isinstance(r.get(key), bool)
        ]
        if not hist:
            continue
        med = statistics.median(hist)
        if med == 0:
            continue
        delta_pct = (float(val) - med) / abs(med) * 100.0
        if direction == "higher":
            breach = float(val) < med * (1.0 - thr)
        else:
            breach = float(val) > med * (1.0 + thr)
        report["checked"] += 1
        report[f"Regress/{key}"] = {
            "current": float(val),
            "median_prior": med,
            "n_prior": len(hist),
            "delta_pct": round(delta_pct, 2),
            "threshold_pct": round(thr * 100.0, 2),
            "direction": direction,
            "breach": bool(breach),
        }
        if breach:
            report["regressions"].append(key)
    report["value"] = len(report["regressions"])
    report["unit"] = "regressions"
    if report["regressions"]:
        report["status"] = "regressed"
    return report, (4 if report["regressions"] else 0)


def _parse_thresholds(entries) -> dict:
    out = {}
    for entry in entries or []:
        key, _, frac = entry.partition("=")
        try:
            out[key.strip()] = float(frac)
        except ValueError:
            raise SystemExit(f"--threshold expects KEY=FRACTION, got {entry!r}")
    return out


if __name__ == "__main__":
    import argparse
    import os

    if os.environ.get("_SHEEPRL_BENCH_FSDP_CHILD"):
        # subprocess body of bench_fsdp: the parent set XLA_FLAGS for the
        # 8-device virtual mesh and a pinned program ledger before spawning us
        print("FSDP_BENCH " + json.dumps(_fsdp_child_main(int(os.environ["_SHEEPRL_BENCH_FSDP_CHILD"]))))
        sys.exit(0)

    if os.environ.get("_SHEEPRL_BENCH_CKPT_CHILD"):
        # subprocess body of bench_checkpoint: the parent pinned the CPU
        # backend and the 8-device virtual mesh before spawning us
        print("CKPT_BENCH " + json.dumps(_ckpt_child_main(int(os.environ["_SHEEPRL_BENCH_CKPT_CHILD"]))))
        sys.exit(0)

    parser = argparse.ArgumentParser(description="sheeprl-tpu bench harness (one JSON line on stdout)")
    parser.add_argument(
        "--target",
        choices=(
            "ppo",
            "dv3",
            "compile",
            "health",
            "orchestrate",
            "serve",
            "serve_fleet",
            "transport",
            "ingraph",
            "ingraph_train",
            "telemetry",
            "fsdp",
            "checkpoint",
            "population",
            "all",
        ),
        default="all",
        help="which workload(s) to run on the accelerator",
    )
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="tiny PPO pass over both buffer backends, held to the CPU (the declared CPU "
        "self-test of the harness: no accelerator, no comparable numbers)",
    )
    parser.add_argument(
        "--check-regressions",
        action="store_true",
        help="run NO workload: compare the newest ledger round's SPS/MFU/p99/peak-HBM "
        "against the median of prior rounds and exit 4 on a breach (the CI gate)",
    )
    parser.add_argument(
        "--ledger",
        default=None,
        help=f"persistent cross-run ledger path (default: benchmarks/ledger.jsonl next "
        f"to bench.py, or ${_LEDGER_ENV})",
    )
    parser.add_argument(
        "--threshold",
        action="append",
        default=[],
        metavar="METRIC=FRACTION",
        help="per-metric sentinel threshold override for --check-regressions "
        "(repeatable; e.g. --threshold serve_p99_ms=0.5)",
    )
    cli_args = parser.parse_args()

    if cli_args.check_regressions:
        # a pure ledger read: no backend discovery, no watchdog, no jax import
        report, rc = check_regressions(
            _ledger_path(cli_args.ledger), _parse_thresholds(cli_args.threshold)
        )
        print(json.dumps(report))
        sys.exit(rc)
    headline_metric = _target_metric("smoke" if cli_args.smoke else cli_args.target)

    # One process for each chip (module docstring). In-process targets
    # initialise JAX here and must find an accelerator; child-run targets keep
    # this process off the chip, so their children can have it.
    in_process = cli_args.smoke or cli_args.target in _INPROC_TARGETS
    if cli_args.smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"  # the declared CPU self-test
    import jax

    device = None
    if in_process:
        dev0 = jax.devices()[0]
        device = {"platform": dev0.platform, "device_kind": dev0.device_kind, "device_count": len(jax.devices())}
        if not cli_args.smoke:
            _require_accelerator(dev0.platform, f"--target {cli_args.target}")
    else:
        # this process only: children inherit the ENVIRONMENT, which is left alone
        jax.config.update("jax_platforms", "cpu")

    errors = {}
    # stdout must carry EXACTLY one JSON line: the CLI's config dump and progress
    # prints go to stderr instead
    with contextlib.redirect_stdout(sys.stderr):
        if cli_args.smoke:
            result = bench_smoke()
        else:
            result = {}
            if cli_args.target in ("ppo", "all"):
                result = bench_ppo()
            if cli_args.target in ("dv3", "all"):
                try:
                    dv3 = bench_dv3()
                    result.update(dv3)
                    if cli_args.target == "dv3":
                        result.setdefault("metric", headline_metric)
                        result.setdefault("value", dv3.get("dv3_gsteps_per_sec"))
                        result.setdefault("unit", "g-steps/s")
                        result.setdefault("vs_baseline", dv3.get("dv3_vs_baseline"))
                except Exception as e:  # keep the PPO number on stdout; the exit code still fails
                    errors["dv3_error"] = f"{type(e).__name__}: {e}"
                try:
                    # the Atari-100K training recipe shape (batch 16 x seq 64)
                    result.update(bench_dv3(batch=16, key_prefix="dv3_recipe"))
                except Exception as e:
                    errors["dv3_recipe_error"] = f"{type(e).__name__}: {e}"
            if cli_args.target == "compile":
                # not part of "all": its children need the chip this process
                # would be holding after the in-process workloads
                comp = bench_compile()
                result.update(comp)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", comp.get("compile_warm_first_train_step_s"))
                result.setdefault("unit", "s")
                result.setdefault("vs_baseline", comp.get("compile_warm_speedup"))
            if cli_args.target == "health":
                # opt-in only (not part of "all"): a CPU-backend resilience
                # drill, not an accelerator throughput number
                health = bench_health()
                result.update(health)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", health.get("health_detection_latency_s"))
                result.setdefault("unit", "s")
            if cli_args.target == "orchestrate":
                # opt-in only, like health: a CPU-backend fleet drill measuring
                # the population controller, not the accelerator
                orch = bench_orchestrate()
                result.update(orch)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", orch.get("orchestrate_preempt_recovery_s"))
                result.setdefault("unit", "s")
            if cli_args.target == "serve":
                # opt-in only: offered-QPS sweep over the policy-serving
                # runtime (subprocess server on the session's backend)
                sv = bench_serve()
                result.update(sv)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", sv.get("serve_p99_ms"))
                result.setdefault("unit", "ms")
                result.setdefault("vs_baseline", None)
            if cli_args.target == "serve_fleet":
                # opt-in only: SLO-gated availability sweep through the
                # failover router while the replica fleet absorbs a SIGKILL
                # and a rolling certified deploy (CPU-backend chaos drill)
                svf = bench_serve_fleet()
                result.update(svf)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", svf.get("serve_fleet_p99_ms"))
                result.setdefault("unit", "ms")
                result.setdefault("vs_baseline", None)
            if cli_args.target == "ingraph":
                # opt-in only: head-to-head of the in-graph vectorized backend
                # (envs/ingraph/) against the host gym path on the same algo
                # settings; the headline is the rollout-phase env-steps/s
                ig = bench_ingraph()
                result.update(ig)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", ig.get("ingraph_env_steps_per_sec"))
                result.setdefault("unit", "env-steps/s")
                result.setdefault("vs_baseline", ig.get("ingraph_vs_host_x"))
            if cli_args.target == "ingraph_train":
                # opt-in only: the whole-iteration fused trainer (collect + GAE
                # + update in one program) vs the same-session collect-only
                # number — the aggregate-throughput headline for the fused path
                igt = bench_ingraph_train()
                result.update(igt)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", igt.get("ingraph_fused_train_env_steps_per_sec"))
                result.setdefault("unit", "env-steps/s")
                result.setdefault("vs_baseline", igt.get("vs_baseline"))
            if cli_args.target == "telemetry":
                # opt-in only: span-tracer overhead on the AOT-warmed fused
                # PPO loop (spans-on vs spans-off vs no-seams baseline) with
                # MFU auto-computed from the executable's own cost_analysis
                tel = bench_telemetry()
                result.update(tel)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", tel.get("telemetry_tracer_overhead_pct"))
                result.setdefault("unit", "%")
                result.setdefault("vs_baseline", None)
            if cli_args.target == "fsdp":
                # opt-in only: DDP-vs-FSDP-vs-overlap step time + per-shard
                # handoff bytes on the 8-device virtual mesh (subprocess child;
                # folds the retired scripts/fsdp_bench.py into the sentinel)
                fs = bench_fsdp()
                result.update(fs)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", fs.get("fsdp_handoff_bytes_per_iter"))
                result.setdefault("unit", "bytes/iter")
                result.setdefault("vs_baseline", fs.get("fsdp_handoff_reduction_x"))
            if cli_args.target == "checkpoint":
                # opt-in only: sharded-checkpoint drill on the 8-device
                # virtual mesh (subprocess child) — train-thread blocked ms
                # (async vs legacy), commit-to-visible latency, elastic
                # 8->2-device restore wall, and the peer-RAM fetch wall
                ckb = bench_checkpoint()
                result.update(ckb)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", ckb.get("checkpoint_blocked_save_ms"))
                result.setdefault("unit", "ms")
                result.setdefault("vs_baseline", ckb.get("checkpoint_blocked_reduction_x"))
            if cli_args.target == "population":
                # opt-in only: the device-resident vmapped PBT population
                # (one compiled program, one trainee process) vs the classic
                # subprocess-per-trial fleet at the same training budget, plus
                # the forced-8-device member-sharded mesh scaling (subprocess
                # children on the CPU backend)
                pop = bench_population()
                result.update(pop)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", pop.get("population_agg_env_steps_per_sec"))
                result.setdefault("unit", "env-steps/s")
                result.setdefault("vs_baseline", pop.get("population_fused_speedup_x"))
            if cli_args.target == "transport":
                # opt-in only: host control-plane latency/throughput drill
                # (sockets + failpoints; no accelerator involved at all)
                tr = bench_transport()
                result.update(tr)
                result.setdefault("metric", headline_metric)
                result.setdefault("value", tr.get("transport_chunk_roundtrip_ms"))
                result.setdefault("unit", "ms")
                result.setdefault("vs_baseline", None)
    if errors:
        # a caught phase failure: the partial record still reaches stdout, but
        # it is not a round (no ledger row) and the exit code says so
        result.update(errors, status="error")
        result.update(_provenance())
        print(json.dumps(result))
        sys.exit(1)
    if device is not None:
        result.update(device)  # every in-process record names the device it ran on
    result.setdefault("status", "ok")
    result.update(_provenance())
    try:
        # peak HBM across devices (null on backends without memory_stats, i.e.
        # CPU): the regression sentinel's memory-footprint signal
        from sheeprl_tpu.telemetry.device import hbm_gauges

        _peak = hbm_gauges().get("Device/hbm_peak_bytes_max")
        if _peak is not None:
            result["device_hbm_peak_bytes"] = _peak
    except Exception:
        pass
    _append_ledger(dict(result), cli_args.ledger)
    print(json.dumps(result))
