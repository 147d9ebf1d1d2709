"""Component-level timing of the DV3-S train step at the bench shape.

Times each phase as its own jit and reports each part's XLA-estimated FLOPs and
achieved MFU, so the slow parts are identified by DATA rather than guesswork.
Fusion across phases is lost in the per-part jits, so the parts need not sum to
the fused step — the point is each part's distance from the roofline.

Every timed window is also recorded as a span in the unified telemetry tracer
(telemetry/trace.py): the closing per-phase table is segmented FROM the
recorded spans (the tracer is the source of truth, not script-local floats),
and the whole run exports as one Chrome/Perfetto trace whose trace id
correlates with any enclosing run's telemetry.

Usage: python scripts/dv3_breakdown.py [batch] [seq] [kernels]

``kernels`` feeds ``algo.world_model.kernels`` (off/auto/pallas/interpret/
reference) — run the script twice (off vs auto) to see what the fused RSSM
step kernels do to the dynamic-scan and world-model fwd+bwd phases.
"""

from __future__ import annotations

import sys
import time

sys.path.insert(0, ".")

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_fn
from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
from sheeprl_tpu.config.loader import load_config
from sheeprl_tpu.core.runtime import Runtime
from sheeprl_tpu.telemetry import trace

from bench import _chip_peak_flops  # per-chip bf16 peak table (repo root)

_PEAK = None  # resolved from the live device in main(); NaN MFU on unknown chips
_PHASE = "dv3.phase/"  # span-name prefix the closing table aggregates on


def _fence(out):
    # fence with a real host pull of ONE scalar reduced on device (np.asarray of
    # the full leaf would pull GBs)
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


def _flops(jitted, *args):
    try:
        compiled = jitted.lower(*args).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return float(cost.get("flops", 0.0)) or None
    except Exception:
        return None


def timeit(label, fn, *args, iters=10):
    jitted = jax.jit(fn) if not hasattr(fn, "lower") else fn
    fl = _flops(jitted, *args)
    out = jitted(*args)
    _fence(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jitted(*args)
    _fence(out)
    t1 = time.perf_counter()
    dt = (t1 - t0) / iters
    trace.add_span(
        f"{_PHASE}{label}", t0, t1, clock="perf", plane="bench", iters=iters, flops=fl
    )
    mfu = fl / dt / _PEAK if fl else float("nan")
    print(f"{label:>28}: {dt*1e3:8.1f} ms  {fl/1e12 if fl else 0:7.3f} TFLOP  MFU={mfu:6.3f}")
    return dt


def _phase_report():
    """Segment per-phase time from the recorded spans — the tracer's ring is
    the single source of truth for what the script just measured."""
    t = trace.get_tracer()
    if t is None:
        return
    rows = [
        (ev[trace._EV_NAME][len(_PHASE):], ev[trace._EV_DUR] / 1e6, (ev[trace._EV_ARGS] or {}))
        for ev in t.events()
        if ev[trace._EV_PH] == "X" and ev[trace._EV_NAME].startswith(_PHASE)
    ]
    if not rows:
        return
    total = sum(dur for _, dur, _ in rows)
    print(f"\nper-phase share (from {len(rows)} tracer spans, trace {t.trace_id}):")
    for name, dur, args in sorted(rows, key=lambda r: -r[1]):
        iters = int(args.get("iters") or 1)
        print(f"{name:>28}: {dur / iters * 1e3:8.1f} ms/iter  {dur / total * 100:5.1f}% of timed wall")
    print(f"trace exported to: {t.export()}")


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    seq = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    kernels = sys.argv[3] if len(sys.argv) > 3 else "off"
    if trace.get_tracer() is None:
        trace.configure(plane="bench", export_path=f"logs/telemetry/dv3_breakdown_b{batch}.trace.json")
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
            "algo.imagination_scan_unroll=15",
            f"algo.world_model.kernels={kernels}",
        ]
    )
    runtime = Runtime(accelerator="auto", devices=1, precision=cfg.fabric.precision)
    global _PEAK
    _PEAK = _chip_peak_flops(runtime.device) or float("nan")
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    actions_dim = (6,)
    modules, params, _ = build_agent(runtime, actions_dim, False, cfg, obs_space)
    rssm = modules.rssm
    rng = np.random.default_rng(0)
    T, B, A = seq, batch, 6

    # ---- FULL fused step FIRST, in a clean HBM state: with the part-timing
    # intermediates alive (~1 GB at batch 128) the fused step degrades to HBM
    # spill-thrash (observed 1.7-3.1 s/step vs the true ~116 ms). A host copy of
    # the params feeds it so donation cannot eat the tree the parts need after.
    host_params = jax.device_get(params)
    init_opt, train_fn = make_train_fn(modules, cfg, runtime, False, actions_dim)
    pr = jax.device_put(host_params)
    opt_states = runtime.replicate(init_opt(pr))
    moments = init_moments()
    batches = {
        "rgb": jax.device_put(rng.integers(0, 255, (1, T, B, 3, 64, 64), dtype=np.uint8)),
        "actions": jax.device_put(rng.random((1, T, B, A), dtype=np.float32)),
        "rewards": jax.device_put(rng.random((1, T, B, 1), dtype=np.float32)),
        "terminated": jax.device_put(np.zeros((1, T, B, 1), np.float32)),
        "truncated": jax.device_put(np.zeros((1, T, B, 1), np.float32)),
        "is_first": jax.device_put(np.zeros((1, T, B, 1), np.float32)),
    }
    key = jax.random.PRNGKey(0)
    state = [pr, opt_states, moments, np.int32(0)]

    def full(batches, key):
        state[0], state[1], state[2], state[3], _flat, m = train_fn(state[0], state[1], state[2], state[3], batches, key)
        return m

    fl = _flops(train_fn, state[0], state[1], state[2], state[3], batches, key)
    for _ in range(2):
        full(batches, key)
    _fence(state[3])
    t0 = time.perf_counter()
    for _ in range(10):
        full(batches, key)
    _fence(state[3])
    t1 = time.perf_counter()
    trace.add_span(
        f"{_PHASE}FULL fused train step", t0, t1, clock="perf", plane="bench", iters=10, flops=fl
    )
    dt = (t1 - t0) / 10
    mfu = fl / dt / _PEAK if fl else float("nan")
    print(f"{'FULL fused train step':>28}: {dt*1e3:8.1f} ms  {fl/1e12 if fl else 0:7.3f} TFLOP  MFU={mfu:6.3f}")
    print("  (NOTE: XLA cost analysis does not scale lax.scan body flops by trip")
    print("   count — the T-step dynamic scan is undercounted (the imagination")
    print("   scan IS counted here because this config fully unrolls it), so the")
    print("   true model-flops MFU is HIGHER than this XLA-estimate figure.)")
    del state, pr, opt_states, moments, batches
    train_fn = None

    # ---- per-part timings (each its own jit; fusion across parts is lost)
    obs = jax.device_put((rng.random((T, B, 3, 64, 64), np.float32) - 0.5).astype(np.float32))
    actions = jax.device_put(rng.random((T, B, A), np.float32).astype(np.float32))
    is_first = jax.device_put(np.zeros((T, B, 1), np.float32))
    key = jax.random.PRNGKey(0)
    wm = params["world_model"]

    enc = jax.jit(lambda p, o: modules.encoder.apply(p["encoder"], {"rgb": o}))
    embedded = enc(wm, obs)
    timeit("encoder fwd", enc, wm, obs)

    dyn = jax.jit(lambda p, e, a, f, k: rssm.dynamic_scan(p, e, a, f, k))
    rs, post, pl, ql = dyn(wm, embedded, actions, is_first, key)
    timeit(f"dynamic_scan fwd (T={T})", dyn, wm, embedded, actions, is_first, key)

    latents = jnp.concatenate([post.reshape(*post.shape[:-2], -1), rs], axis=-1)
    dec = jax.jit(lambda p, z: modules.observation_model.apply(p["observation_model"], z))
    timeit("decoder fwd", dec, wm, latents)

    heads = jax.jit(
        lambda p, z: (
            modules.reward_model.apply(p["reward_model"], z),
            modules.continue_model.apply(p["continue_model"], z),
        )
    )
    timeit("reward+continue heads fwd", heads, wm, latents)

    # world-model fwd+bwd: the reconstruction phase as one value_and_grad
    def wm_loss(p, o, a, f, k):
        e = modules.encoder.apply(p["encoder"], {"rgb": o})
        rs_, post_, _, _ = rssm.dynamic_scan(p, e, a, f, k)
        z = jnp.concatenate([post_.reshape(*post_.shape[:-2], -1), rs_], axis=-1)
        recon = modules.observation_model.apply(p["observation_model"], z)["rgb"]
        rew = modules.reward_model.apply(p["reward_model"], z)
        cont = modules.continue_model.apply(p["continue_model"], z)
        return (
            jnp.mean((recon.astype(jnp.float32) - o) ** 2)
            + jnp.mean(rew.astype(jnp.float32) ** 2)
            + jnp.mean(cont.astype(jnp.float32) ** 2)
        )

    wm_grad = jax.jit(jax.grad(wm_loss))
    timeit("world-model fwd+bwd", wm_grad, wm, obs, actions, is_first, key)

    # imagination: H steps over T*B rows
    start_prior = post.reshape(1, -1, rssm.stoch_state_size)[0]
    start_rec = rs.reshape(1, -1, rs.shape[-1])[0]
    H = int(cfg.algo.horizon)

    def imagine(p, ap, sp, sr, k):
        def step(carry, kk):
            pf, rec = carry
            k1, k2 = jax.random.split(kk)
            prior, rec = rssm.imagination_step(p, pf, rec, jnp.zeros((sp.shape[0], A), jnp.float32), k1)
            return (prior.reshape(pf.shape), rec), prior

        return jax.lax.scan(step, (sp, sr), jax.random.split(k, H), unroll=H)[1]

    timeit(f"imagination scan (H={H} fwd)", jax.jit(imagine), wm, params["actor"], start_prior, start_rec, key)

    _phase_report()


if __name__ == "__main__":
    main()
