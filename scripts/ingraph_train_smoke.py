#!/usr/bin/env python
"""Fused in-graph TRAINING smoke: whole-iteration fused PPO, single + sharded.

Two fresh interpreters each train PPO on the in-graph CartPole with the
whole-iteration fused step (``envs/ingraph/fused.py``: rollout scan + GAE +
update epochs in ONE donated-carry program):

- ``fused``:   single device, three iterations (warmup + two steady-state);
- ``sharded``: the ``shard_map`` variant on a 2-device virtual CPU mesh
  (``--xla_force_host_platform_device_count=2`` + ``fabric.devices=2``), env
  batch sharded on the ``data`` axis, grads pmean'd in-graph.

Each child must finish with ZERO retraces — the fused entry point, its AOT
warmup spec, and the mesh placements all agree on one abstract signature, or
the fused wiring (envs/ingraph/ + algos/ppo + core/compile.py) has drifted —
and must then play finite-return episodes through the debug step path (the
cheap "training left a working policy/env behind" signal).

Run directly (``python scripts/ingraph_train_smoke.py``) or through the
registered tier-1 test (tests/test_utils/test_ingraph_train_smoke.py).
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

overrides = json.loads(os.environ["_SHEEPRL_INGRAPH_TRAIN_SMOKE_OVERRIDES"])
with contextlib.redirect_stdout(sys.stderr):
    run(overrides=overrides)
stats = jax_compile.process_stats()
fused_stats = {
    name: s for name, s in stats["functions"].items()
    if name.endswith(".ingraph_train")
}

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

print("INGRAPH_TRAIN_SMOKE " + json.dumps({
    "retraces": stats["retraces"],
    "traces": stats["traces"],
    "aot_compiles": stats["aot_compiles"],
    "fused_calls": sum(s["calls"] for s in fused_stats.values()),
    "n_episodes": len(returns),
    "mean_return": (sum(returns) / len(returns)) if returns else None,
}), flush=True)
"""

_BASE_OVERRIDES = [
    "exp=ppo",
    "env=jax_cartpole",
    "env.fused=True",
    "env.num_envs=16",
    "algo.rollout_steps=16",
    "algo.per_rank_batch_size=128",
    "algo.update_epochs=1",
    "algo.mlp_keys.encoder=[state]",
    "algo.cnn_keys.encoder=[]",
    "algo.run_test=False",
    "metric.log_level=0",
    "metric.disable_timer=True",
    "checkpoint.every=999999999",
    "checkpoint.save_last=False",
    "buffer.memmap=False",
]

# 3 iterations each: warmup + two steady-state (the retrace check needs >= 2
# post-warmup calls to catch a signature that only stabilizes after the first)
VARIANTS = {
    "fused": {
        "overrides": _BASE_OVERRIDES + ["fabric.devices=1", "algo.total_steps=768"],
        "devices": 1,
    },
    "sharded": {
        # world_size=2 doubles the driven env batch (n_envs = num_envs * world)
        "overrides": _BASE_OVERRIDES + ["fabric.devices=2", "algo.total_steps=1536"],
        "devices": 2,
    },
}


def _run_variant(name: str, spec: dict, workdir: str, timeout: float) -> dict:
    xla_flags = [
        f
        for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    xla_flags.append(f"--xla_force_host_platform_device_count={spec['devices']}")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=" ".join(xla_flags),
        PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_COMPILATION_CACHE_DIR=os.path.join(workdir, "xla_cache"),
        _SHEEPRL_INGRAPH_TRAIN_SMOKE_OVERRIDES=json.dumps(spec["overrides"]),
    )
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD],
        cwd=workdir,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    tag = "INGRAPH_TRAIN_SMOKE "
    line = next((ln for ln in proc.stdout.splitlines() if ln.startswith(tag)), None)
    if proc.returncode != 0 or line is None:
        raise SystemExit(
            f"'{name}' child failed (rc={proc.returncode});\nstdout tail:\n{proc.stdout[-1000:]}"
            f"\nstderr tail:\n{proc.stderr[-3000:]}"
        )
    stats = json.loads(line[len(tag):])

    if stats["retraces"] != 0:
        raise SystemExit(f"'{name}': retraces during the fused train smoke: {stats['retraces']}")
    if stats["fused_calls"] < 3:
        raise SystemExit(f"'{name}': fused entry point ran {stats['fused_calls']} times, expected >= 3")
    if stats["n_episodes"] <= 0:
        raise SystemExit(f"'{name}': no episode finished in 64 random-policy steps x 8 envs")
    if stats["mean_return"] is None or not math.isfinite(stats["mean_return"]):
        raise SystemExit(f"'{name}': non-finite mean episode return: {stats['mean_return']}")
    return stats


def main(workdir: str | None = None, timeout: float = 480.0) -> dict:
    workdir = workdir or tempfile.mkdtemp(prefix="ingraph_train_smoke_")
    os.makedirs(workdir, exist_ok=True)
    results = {
        name: _run_variant(name, spec, workdir, timeout) for name, spec in VARIANTS.items()
    }
    print(f"ingraph train smoke OK: {json.dumps(results)}")
    return results


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workdir", default=None, help="scratch dir (default: a fresh tempdir)")
    parser.add_argument("--timeout", type=float, default=480.0, help="per-child timeout in seconds")
    cli = parser.parse_args()
    main(cli.workdir, cli.timeout)
