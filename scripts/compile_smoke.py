#!/usr/bin/env python
"""Compile smoke: prove the persistent compilation cache end-to-end on CPU.

Runs the same tiny PPO workload twice in fresh interpreters against ONE
temporary on-disk compilation cache:

1. the COLD child starts with an empty cache directory, so every jitted hot
   path (packed act, fused train step, GAE, metric drain) is compiled by XLA
   and written to the cache;
2. the WARM child replays those executables from disk — it must record
   strictly fewer cache misses than the cold child and at least one cache hit,
   or the cache wiring (``sheeprl_tpu/__init__.py`` + ``configs/compile/``) is
   broken.

Each child also reports the retrace-guard totals, so the smoke doubles as an
assertion that two identical runs see identical abstract signatures (zero
steady-state retraces).

Run directly (``python scripts/compile_smoke.py``) or through the registered
tier-1 test (tests/test_utils/test_compile_smoke.py).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import contextlib, json, os, sys
from sheeprl_tpu.cli import run
from sheeprl_tpu.core import compile as jax_compile

overrides = json.loads(os.environ["_SHEEPRL_COMPILE_SMOKE_OVERRIDES"])
with contextlib.redirect_stdout(sys.stderr):
    run(overrides=overrides)
stats = jax_compile.process_stats()
print("COMPILE_SMOKE " + json.dumps({
    "cache_hits": stats["cache_hits"],
    "cache_misses": stats["cache_misses"],
    "retraces": stats["retraces"],
    "traces": stats["traces"],
    "aot_compiles": stats["aot_compiles"],
}), flush=True)
"""

OVERRIDES = [
    "exp=ppo",
    "algo.total_steps=64",
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


def _run_child(env: dict, workdir: str, timeout: float) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD],
        cwd=workdir,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    line = next((ln for ln in proc.stdout.splitlines() if ln.startswith("COMPILE_SMOKE ")), None)
    if proc.returncode != 0 or line is None:
        raise SystemExit(
            f"child run failed (rc={proc.returncode});\nstdout tail:\n{proc.stdout[-1000:]}"
            f"\nstderr tail:\n{proc.stderr[-3000:]}"
        )
    return json.loads(line[len("COMPILE_SMOKE "):])


def main(workdir: str | None = None, timeout: float = 480.0) -> dict:
    workdir = workdir or tempfile.mkdtemp(prefix="compile_smoke_")
    os.makedirs(workdir, exist_ok=True)
    cache_dir = os.path.join(workdir, "xla_cache")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        # the smoke's kernels are tiny and compile in milliseconds: cache them
        # all, or the warm pass would legitimately miss everything
        SHEEPRL_TPU_COMP_CACHE_MIN_SECS="0",
        _SHEEPRL_COMPILE_SMOKE_OVERRIDES=json.dumps(OVERRIDES),
    )
    cold = _run_child(env, workdir, timeout)
    if not os.listdir(cache_dir):
        raise SystemExit(f"cold run left the persistent cache at {cache_dir} empty")
    warm = _run_child(env, workdir, timeout)

    if warm["cache_misses"] >= cold["cache_misses"]:
        raise SystemExit(
            f"warm run recompiled as much as the cold one: cold misses="
            f"{cold['cache_misses']}, warm misses={warm['cache_misses']}"
        )
    if warm["cache_hits"] <= 0:
        raise SystemExit("warm run served zero executables from the persistent cache")
    if warm["retraces"] != 0 or cold["retraces"] != 0:
        raise SystemExit(f"retraces during the smoke: cold={cold['retraces']}, warm={warm['retraces']}")

    result = {"cold": cold, "warm": warm, "cache_dir": cache_dir}
    print(f"compile smoke OK: {json.dumps(result)}")
    return result


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workdir", default=None, help="scratch dir (default: a fresh tempdir)")
    parser.add_argument("--timeout", type=float, default=480.0, help="per-child timeout in seconds")
    cli = parser.parse_args()
    main(cli.workdir, cli.timeout)
