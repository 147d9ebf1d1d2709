"""bench.py --smoke: the in-process harness check the suite actually runs.

The real bench targets need an accelerator; the smoke mode is the one
path that keeps the harness from bit-rotting unnoticed, so it is pinned here
as a plain (non-slow) test — covering BOTH on-policy buffer backends.
"""

import json
import subprocess
import sys

import pytest

import bench


def test_bench_smoke_runs_both_backends(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    result = bench.bench_smoke(total_steps=64)
    assert result["smoke"] is True
    assert result["metric"] == "ppo_smoke_env_steps_per_sec"
    for backend in ("host", "device"):
        rate = result[f"smoke_{backend}_env_steps_per_sec"]
        assert rate > 0, f"{backend} backend produced a non-positive rate"
    assert result["value"] == result["smoke_host_env_steps_per_sec"]
    json.dumps(result)  # the bench contract: one JSON-serializable dict


def test_target_metric_names():
    assert bench._target_metric("ppo") == "ppo_cartpole_env_steps_per_sec"
    assert bench._target_metric("dv3") == "dv3_gsteps_per_sec"
    assert bench._target_metric("smoke") == "ppo_smoke_env_steps_per_sec"
    assert bench._target_metric("all") == "ppo_cartpole_env_steps_per_sec"
    with pytest.raises(KeyError):
        bench._target_metric("nope")


@pytest.mark.slow
def test_bench_smoke_cli_emits_one_json_line(tmp_path):
    """End-to-end stdout contract: `python bench.py --smoke` prints EXACTLY one
    line on stdout and it is the result JSON (driver parses stdout verbatim)."""
    out = subprocess.run(
        [sys.executable, str(bench.__file__), "--smoke"],
        cwd=tmp_path,
        capture_output=True,
        text=True,
        timeout=600,
        check=True,
    )
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"bench --smoke stdout must be one JSON line, got: {lines}"
    result = json.loads(lines[0])
    assert result["smoke"] is True and result["value"] > 0
