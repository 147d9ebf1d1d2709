#!/usr/bin/env python
"""In-graph backend smoke: one short PPO run with ``env.backend=ingraph``.

A fresh interpreter trains PPO on the in-graph CartPole for two iterations
(warmup + steady state) and must finish with ZERO retraces — the fused
``lax.scan`` collector, the train step, and the AOT warmup all agree on their
abstract signatures, or the backend wiring (envs/ingraph/ + data/factory.py +
the algo loops) has drifted. The child then drives the debug ``venv.step``
path with a random policy and reports the finished-episode returns, which must
be finite and non-empty — the cheap end-to-end "the env actually plays
episodes" signal.

Run directly (``python scripts/ingraph_smoke.py``) or through the registered
tier-1 test (tests/test_utils/test_ingraph_smoke.py).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import contextlib, json, os, sys
import numpy as np
from sheeprl_tpu.cli import run
from sheeprl_tpu.core import compile as jax_compile

overrides = json.loads(os.environ["_SHEEPRL_INGRAPH_SMOKE_OVERRIDES"])
with contextlib.redirect_stdout(sys.stderr):
    run(overrides=overrides)
stats = jax_compile.process_stats()

# the mesh-training chaos seams must have been exercised: the per-shard
# rollout handoff put and the microbatched grad-sync dispatch both carry
# armed `fire` failpoints (SHEEPRL_TPU_FAILPOINTS, set by the parent)
from sheeprl_tpu.core import failpoints

fp_fires = {name: c["fires"] for name, c in failpoints.counts().items()}

# random-policy drive through the debug step path: episodes must finish with
# finite returns (auto-reset keeps every env alive the whole time)
from sheeprl_tpu.config import load_config
from sheeprl_tpu.envs import ingraph as ig

with contextlib.redirect_stdout(sys.stderr):
    cfg = load_config(overrides=overrides)
    venv = ig.make_vector_env(cfg, 8, 123)
    venv.reset(seed=123)
    rng = np.random.default_rng(0)
    returns = []
    for _ in range(64):
        _obs, _rew, term, trunc, info = venv.step(rng.integers(0, 2, size=(8,)))
        done = np.logical_or(term, trunc)
        returns.extend(float(r) for r in info["episode_returns"][done])

print("INGRAPH_SMOKE " + json.dumps({
    "retraces": stats["retraces"],
    "traces": stats["traces"],
    "aot_compiles": stats["aot_compiles"],
    "n_episodes": len(returns),
    "mean_return": (sum(returns) / len(returns)) if returns else None,
    "failpoint_fires": fp_fires,
}), flush=True)
"""

OVERRIDES = [
    "exp=ppo",
    "env=jax_cartpole",
    "env.num_envs=16",
    "algo.total_steps=512",  # 2 iterations: warmup + one steady-state (retrace check)
    "algo.rollout_steps=16",
    "algo.per_rank_batch_size=128",
    "algo.update_epochs=1",
    "algo.mlp_keys.encoder=[state]",
    "algo.cnn_keys.encoder=[]",
    "algo.run_test=False",
    "algo.grad_microbatches=2",  # the accumulation scan must hold on the fused path too
    "metric.log_level=0",
    "metric.disable_timer=True",
    "checkpoint.every=999999999",
    "checkpoint.save_last=False",
    "buffer.memmap=False",
    "fabric.devices=1",
]


def main(workdir: str | None = None, timeout: float = 480.0) -> dict:
    workdir = workdir or tempfile.mkdtemp(prefix="ingraph_smoke_")
    os.makedirs(workdir, exist_ok=True)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_COMPILATION_CACHE_DIR=os.path.join(workdir, "xla_cache"),
        _SHEEPRL_INGRAPH_SMOKE_OVERRIDES=json.dumps(OVERRIDES),
        # arm the grad-sync chaos seam in benign `fire` mode: the fused run must
        # actually pass through the microbatched update dispatch every iteration
        # (the handoff seam has no site here — fused data never leaves the
        # device; the decoupled FSDP tests drill handoff.shard_put instead)
        SHEEPRL_TPU_FAILPOINTS="train.grad_sync:fire",
    )
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD],
        cwd=workdir,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    line = next((ln for ln in proc.stdout.splitlines() if ln.startswith("INGRAPH_SMOKE ")), None)
    if proc.returncode != 0 or line is None:
        raise SystemExit(
            f"child run failed (rc={proc.returncode});\nstdout tail:\n{proc.stdout[-1000:]}"
            f"\nstderr tail:\n{proc.stderr[-3000:]}"
        )
    stats = json.loads(line[len("INGRAPH_SMOKE "):])

    if stats["retraces"] != 0:
        raise SystemExit(f"retraces during the ingraph smoke: {stats['retraces']}")
    if stats["n_episodes"] <= 0:
        raise SystemExit("no episode finished in 64 random-policy steps x 8 envs")
    if stats["mean_return"] is None or not math.isfinite(stats["mean_return"]):
        raise SystemExit(f"non-finite mean episode return: {stats['mean_return']}")
    fires = stats.get("failpoint_fires") or {}
    if int(fires.get("train.grad_sync", 0)) < 1:
        raise SystemExit(
            "failpoint 'train.grad_sync' never fired during the smoke — the run did "
            f"not pass through the grad-sync dispatch seam (fires: {json.dumps(fires)})"
        )

    print(f"ingraph smoke OK: {json.dumps(stats)}")
    return stats


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workdir", default=None, help="scratch dir (default: a fresh tempdir)")
    parser.add_argument("--timeout", type=float, default=480.0, help="child timeout in seconds")
    cli = parser.parse_args()
    main(cli.workdir, cli.timeout)
