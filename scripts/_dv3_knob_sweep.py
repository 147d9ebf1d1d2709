"""One-off sweep of DV3 precision/unroll knobs at the bench shape (see task log)."""

import sys
import time

sys.path.insert(0, ".")

import gymnasium as gym
import jax
import numpy as np

from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3 import make_train_fn
from sheeprl_tpu.algos.dreamer_v3.utils import init_moments
from sheeprl_tpu.config.loader import load_config
from sheeprl_tpu.core.runtime import Runtime
from sheeprl_tpu.telemetry.device import chip_peak_flops


def run(label, extra, batch=128):
    cfg = load_config(
        overrides=[
            "exp=dreamer_v3",
            "algo=dreamer_v3_S",
            "env=dummy",
            f"algo.per_rank_batch_size={batch}",
            "algo.per_rank_sequence_length=64",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.cnn_keys.decoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "algo.mlp_keys.decoder=[]",
            *extra,
        ]
    )
    runtime = Runtime(accelerator="auto", devices=1, precision=cfg.fabric.precision)
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    modules, params, _ = build_agent(runtime, (6,), False, cfg, obs_space)
    init_opt, train_fn = make_train_fn(modules, cfg, runtime, False, (6,))
    opt = runtime.replicate(init_opt(params))
    pr = runtime.replicate(params)
    mom = init_moments()
    cnt = np.int32(0)
    rng = np.random.default_rng(0)
    T, B, A = 64, batch, 6
    batches = {
        "rgb": jax.device_put(rng.integers(0, 255, (1, T, B, 3, 64, 64), dtype=np.uint8)),
        "actions": jax.device_put(rng.random((1, T, B, A), dtype=np.float32)),
        "rewards": jax.device_put(rng.random((1, T, B, 1), dtype=np.float32)),
        "terminated": jax.device_put(np.zeros((1, T, B, 1), np.float32)),
        "truncated": jax.device_put(np.zeros((1, T, B, 1), np.float32)),
        "is_first": jax.device_put(np.zeros((1, T, B, 1), np.float32)),
    }
    key = jax.random.PRNGKey(0)
    try:
        flops = None
        try:
            compiled = train_fn.lower(pr, opt, mom, cnt, batches, key).compile()
            c = compiled.cost_analysis()
            c = c[0] if isinstance(c, (list, tuple)) else c
            flops = float(c.get("flops", 0.0)) or None
        except Exception:
            pass
        for _ in range(2):
            pr, opt, mom, cnt, _flat, m = train_fn(pr, opt, mom, cnt, batches, key)
        np.asarray(cnt)
        t0 = time.perf_counter()
        for _ in range(10):
            pr, opt, mom, cnt, _flat, m = train_fn(pr, opt, mom, cnt, batches, key)
        np.asarray(cnt)
        dt = (time.perf_counter() - t0) / 10
        peak = chip_peak_flops(runtime.device)  # None on a chip the table does not know
        mfu = flops / dt / peak if flops and peak else float("nan")
        print(f"{label}: {dt*1e3:.1f} ms/step  flops={flops/1e12 if flops else 0:.2f}T  MFU={mfu:.3f}", flush=True)
    except Exception as e:
        print(f"{label}: FAILED {type(e).__name__}: {str(e)[:160]}", flush=True)


if __name__ == "__main__":
    configs = [
        ("bf16-mixed base", ("fabric.precision=bf16-mixed",)),
        ("bf16-mixed d4", ("fabric.precision=bf16-mixed", "algo.world_model.dynamic_scan_unroll=4")),
        ("bf16-mixed i15", ("fabric.precision=bf16-mixed", "algo.imagination_scan_unroll=15")),
        (
            "bf16-mixed d4+i15",
            (
                "fabric.precision=bf16-mixed",
                "algo.world_model.dynamic_scan_unroll=4",
                "algo.imagination_scan_unroll=15",
            ),
        ),
        ("bf16-true base", ("fabric.precision=bf16-true",)),
        (
            "bf16-true d4+i15",
            (
                "fabric.precision=bf16-true",
                "algo.world_model.dynamic_scan_unroll=4",
                "algo.imagination_scan_unroll=15",
            ),
        ),
    ]
    which = sys.argv[1:] or None
    for label, extra in configs:
        if which and not any(w in label for w in which):
            continue
        run(label, extra)
