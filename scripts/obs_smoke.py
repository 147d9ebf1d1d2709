#!/usr/bin/env python
"""Compiled-program observatory smoke: ledger capture, diff gate, bench sentinel.

Three phases, all in fresh interpreters (the capture path must work from a cold
import, exactly like a real run):

1. **capture** — two tiny fused-PPO iterations on the in-graph CartPole with
   the trace id AND the programs ledger pinned through the env
   (``SHEEPRL_TPU_TRACE`` / ``SHEEPRL_TPU_PROGRAMS``). Every AOT-compiled
   program of the run must land in ``programs.jsonl`` with a non-null
   fingerprint, FLOPs, HBM breakdown and shardings, stamped with the pinned
   trace id — and the fused ``.ingraph_train`` entry point must be among them.
2. **diff** — ``python -m sheeprl_tpu.telemetry.programs diff`` against a
   doctored copy of that ledger (+10% temp-HBM, one resharded input) must exit
   1 and name both regressions; the self-diff must exit 0.
3. **sentinel** — ``python bench.py --check-regressions`` over a synthetic
   4-round ledger must exit 0 clean and 4 after the newest round is doctored
   (SPS halved, p99 quadrupled).

Run directly (``python scripts/obs_smoke.py``) or through the registered
tier-1 test (tests/test_utils/test_obs_smoke.py).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TRACE_ID = "obs-smoke-trace"

_CHILD = r"""
import contextlib, json, os, sys
from sheeprl_tpu.cli import run
from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.telemetry import programs as tel_programs

overrides = json.loads(os.environ["_SHEEPRL_OBS_SMOKE_OVERRIDES"])
with contextlib.redirect_stdout(sys.stderr):
    run(overrides=overrides)

stats = jax_compile.process_stats()
print("OBS_SMOKE " + json.dumps({
    "retraces": stats["retraces"],
    "aot_compiles": stats["aot_compiles"],
    "programs": tel_programs.stats(),
}), flush=True)
"""

# 16 envs x 16 steps = 256 policy steps/iter; 512 total = two fused iterations
_OVERRIDES = [
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
    "algo.total_steps=512",
    "fabric.devices=1",
    "metric.log_level=0",
    "metric.disable_timer=True",
    "checkpoint.every=999999999",
    "checkpoint.save_last=False",
    "buffer.memmap=False",
]


def _child_env(workdir: str, ledger: str) -> dict:
    return dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_COMPILATION_CACHE_DIR=os.path.join(workdir, "xla_cache"),
        SHEEPRL_TPU_TRACE=f"plane=train;capacity=4096;trace_id={_TRACE_ID}",
        SHEEPRL_TPU_PROGRAMS=ledger,
        _SHEEPRL_OBS_SMOKE_OVERRIDES=json.dumps(_OVERRIDES),
    )


def _phase_capture(workdir: str, timeout: float) -> dict:
    ledger = os.path.join(workdir, "programs.jsonl")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD],
        cwd=workdir,
        env=_child_env(workdir, ledger),
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    tag = "OBS_SMOKE "
    line = next((ln for ln in proc.stdout.splitlines() if ln.startswith(tag)), None)
    if proc.returncode != 0 or line is None:
        raise SystemExit(
            f"capture child failed (rc={proc.returncode});\nstdout tail:\n{proc.stdout[-1000:]}"
            f"\nstderr tail:\n{proc.stderr[-3000:]}"
        )
    stats = json.loads(line[len(tag):])
    if stats["retraces"] != 0:
        raise SystemExit(f"capture: retraces during the fused smoke: {stats['retraces']}")
    if not os.path.isfile(ledger):
        raise SystemExit(f"capture: no programs ledger written at {ledger}")

    with open(ledger) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    if not rows:
        raise SystemExit("capture: programs ledger is empty")
    if len(rows) < stats["aot_compiles"]:
        raise SystemExit(
            f"capture: {stats['aot_compiles']} AOT compiles but only {len(rows)} ledger rows"
        )
    for row in rows:
        for field in ("fingerprint", "flops", "memory", "input_shardings", "output_shardings"):
            if row.get(field) is None:
                raise SystemExit(f"capture: row for {row.get('name')!r} has null {field}")
        if row.get("trace_id") != _TRACE_ID:
            raise SystemExit(
                f"capture: row for {row.get('name')!r} carries trace_id={row.get('trace_id')!r}, "
                f"expected the pinned {_TRACE_ID!r}"
            )
    names = {row["name"] for row in rows}
    if not any(name.endswith(".ingraph_train") for name in names):
        raise SystemExit(f"capture: no fused .ingraph_train program in the ledger: {sorted(names)}")
    return {"rows": len(rows), "programs": sorted(names), "ledger": ledger}


def _doctor_ledger(ledger: str, out_path: str) -> None:
    with open(ledger) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    for row in rows:
        mem = row.get("memory") or {}
        if "temp_bytes" in mem:
            delta = mem["temp_bytes"] * 0.10 or 4096.0
            mem["temp_bytes"] += delta
            mem["peak_bytes"] = mem.get("peak_bytes", 0.0) + delta
        if row.get("input_shardings"):
            row["input_shardings"] = ["NamedSharding(resharded)"] + row["input_shardings"][1:]
    with open(out_path, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


def _run_cli(args: list, timeout: float) -> subprocess.CompletedProcess:
    env = dict(os.environ, PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable] + args,
        cwd=REPO_ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def _phase_diff(ledger: str, workdir: str, timeout: float) -> dict:
    doctored = os.path.join(workdir, "programs_regressed.jsonl")
    _doctor_ledger(ledger, doctored)

    bad = _run_cli(
        ["-m", "sheeprl_tpu.telemetry.programs", "diff", ledger, doctored, "--json"], timeout
    )
    if bad.returncode != 1:
        raise SystemExit(
            f"diff: doctored ledger must exit 1, got rc={bad.returncode}\n{bad.stdout}\n{bad.stderr[-1000:]}"
        )
    report = json.loads(bad.stdout)
    if not any(d["field"] == "temp_bytes" and d["regression"] for d in report["memory_deltas"]):
        raise SystemExit(f"diff: seeded +10% temp-HBM not flagged: {report['memory_deltas']}")
    if not any(c["io"] == "input_shardings" for c in report["sharding_changes"]):
        raise SystemExit(f"diff: seeded resharding not flagged: {report['sharding_changes']}")

    clean = _run_cli(["-m", "sheeprl_tpu.telemetry.programs", "diff", ledger, ledger], timeout)
    if clean.returncode != 0:
        raise SystemExit(f"diff: self-diff must exit 0, got rc={clean.returncode}\n{clean.stdout}")
    return {"regressions_flagged": len(report["regressions"])}


def _phase_sentinel(workdir: str, timeout: float) -> dict:
    bench_py = os.path.join(REPO_ROOT, "bench.py")
    base = {
        "status": "ok",
        "env_steps_per_sec": 1000.0,
        "infer_p99_ms": 10.0,
        "device_hbm_peak_bytes": 1.0e9,
    }
    ledger = os.path.join(workdir, "bench_ledger.jsonl")
    with open(ledger, "w") as f:
        for i in range(4):
            f.write(json.dumps(dict(base, run_id=f"r{i}")) + "\n")
    clean = _run_cli([bench_py, "--check-regressions", "--ledger", ledger], timeout)
    if clean.returncode != 0:
        raise SystemExit(
            f"sentinel: clean ledger must exit 0, got rc={clean.returncode}\n{clean.stdout}\n{clean.stderr[-500:]}"
        )
    with open(ledger, "a") as f:
        f.write(
            json.dumps(dict(base, run_id="bad", env_steps_per_sec=500.0, infer_p99_ms=40.0)) + "\n"
        )
    bad = _run_cli([bench_py, "--check-regressions", "--ledger", ledger], timeout)
    if bad.returncode != 4:
        raise SystemExit(
            f"sentinel: doctored ledger must exit 4, got rc={bad.returncode}\n{bad.stdout}\n{bad.stderr[-500:]}"
        )
    report = json.loads(bad.stdout.splitlines()[-1])
    for key in ("env_steps_per_sec", "infer_p99_ms"):
        if key not in report["regressions"]:
            raise SystemExit(f"sentinel: {key} breach not reported: {report['regressions']}")
    return {"clean_rc": clean.returncode, "doctored_rc": bad.returncode}


def main(workdir: str | None = None, timeout: float = 480.0) -> dict:
    workdir = workdir or tempfile.mkdtemp(prefix="obs_smoke_")
    os.makedirs(workdir, exist_ok=True)
    results = {"capture": _phase_capture(workdir, timeout)}
    results["diff"] = _phase_diff(results["capture"]["ledger"], workdir, timeout)
    results["sentinel"] = _phase_sentinel(workdir, timeout)
    print(f"obs smoke OK: {json.dumps(results)}")
    return results


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workdir", default=None, help="scratch dir (default: a fresh tempdir)")
    parser.add_argument("--timeout", type=float, default=480.0, help="per-phase timeout in seconds")
    cli = parser.parse_args()
    main(cli.workdir, cli.timeout)
