"""Real 2-process jax.distributed test over localhost (CPU backend).

Reference counterpart: the reference proves its distributed path with CPU-Gloo
multi-process launches (tests/test_algos/test_algos.py `devices` fixture); here two
subprocesses form a jax.distributed world and the test asserts log-dir broadcast,
DP gradient agreement, and checkpoint write-once.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

_CHILD = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "multihost_child.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


@pytest.mark.timeout(300)
def test_two_process_distributed(tmp_path):
    by_pid, workdir = _run_children(_free_port(), 2, tmp_path)
    assert set(by_pid) == {0, 1}

    # rank-0's versioned log dir reached every process
    assert by_pid[0]["log_dir"] == by_pid[1]["log_dir"]
    assert "version_0" in by_pid[0]["log_dir"]

    # DP gradients agree bit-for-bit across processes (XLA allreduce), and they are
    # nonzero (i.e. the comparison is not trivially 0 == 0)
    g0, g1 = np.asarray(by_pid[0]["grad"]), np.asarray(by_pid[1]["grad"])
    np.testing.assert_array_equal(g0, g1)
    assert np.abs(g0).sum() > 0

    # checkpoint written exactly once (global-zero only), visible to both
    assert by_pid[0]["ckpt_exists"] and by_pid[1]["ckpt_exists"]
    ckpts = [f for f in os.listdir(workdir) if f.startswith("ckpt_")]
    assert len(ckpts) == 1


# XLA's CPU-Gloo collective runtime occasionally aborts a rank mid-collective
# (``gloo::EnforceNotMet ... op.preamble.length <= op.nbytes``) or wedges the
# world when concurrent collectives race on one TCP pair; the peers then die on
# the coordination-service fatal. The race lives in jaxlib's C++ runtime (it
# reproduces at every commit of this repo, CPU backend only) — so a world whose
# failure matches these signatures is retried on a fresh port + workdir, while
# a rank that fails for any other reason (assertion, traceback, bad exit) still
# fails the test on the first attempt.
_INFRA_RACE_SIGNATURES = (
    "gloo::EnforceNotMet",
    "Gloo all-reduce failed",
    "JAX distributed service detected fatal errors",
    "Connection reset by peer",
    "heartbeat timeout",
)


def _spawn_world(port, nproc, workdir, mode, extra_args, timeout, child):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, child, str(port), str(pid), str(nproc), str(workdir)]
            + ([mode] if mode else [])
            + (extra_args[pid] if extra_args else []),
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for pid in range(nproc)
    ]
    results, timed_out = [], False
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
            for q in procs:
                q.kill()
            out, err = p.communicate()
        results.append((p, out, err))
    return results, timed_out


def _run_children(port, nproc, tmp_path, mode=None, extra_args=None, timeout=240, child=_CHILD, attempts=3):
    per_attempt = max(120, timeout // attempts)
    last_report = ""
    for attempt in range(attempts):
        # fresh workdir per attempt: a crashed world may leave partial run dirs
        # and checkpoints behind, which would corrupt version-numbering and
        # write-once assertions on the retry
        workdir = os.path.join(str(tmp_path), f"attempt{attempt}")
        os.makedirs(workdir, exist_ok=True)
        world_port = port if attempt == 0 else _free_port()
        results, timed_out = _spawn_world(
            world_port, nproc, workdir, mode, extra_args, per_attempt, child
        )
        # report every rank, not just the first nonzero one: when one rank dies
        # its peers abort on the coordination fatal, and the peer's stderr only
        # ever says "another task died" — the root cause is in the rank that
        # exited first
        report = "\n".join(
            f"--- rank {i} rc={p.returncode} stdout ---\n{out}\n--- rank {i} stderr ---\n{err}"
            for i, (p, out, err) in enumerate(results)
        )
        if not timed_out and all(p.returncode == 0 for p, _, _ in results):
            outs = [json.loads(out.strip().splitlines()[-1]) for _, out, _ in results]
            return {o["pid"]: o for o in outs}, workdir
        kind = "world timed out" if timed_out else "child failed"
        last_report = f"{kind} (attempt {attempt + 1}/{attempts}):\n{report}"
        if not (timed_out or any(sig in report for sig in _INFRA_RACE_SIGNATURES)):
            break
        print(
            f"[multihost] transient collective-runtime failure, retrying on a fresh world\n{last_report}",
            file=sys.stderr,
        )
    pytest.fail(last_report)


@pytest.mark.timeout(120)
def test_coordinator_absent_times_out_fast(tmp_path):
    """No coordinator listening: the process must fail within the configured
    multihost_timeout_s instead of hanging for jax's 300 s default.

    jax's coordination client aborts the process fatally (absl F-log) on a
    registration deadline rather than raising a catchable exception, so 'fast,
    loud death' IS the detectable failure mode; Runtime's multihost_timeout_s
    is what bounds it."""
    import time

    port = _free_port()  # nobody binds it: process_id=1 waits for a coordinator that never comes
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.monotonic()
    p = subprocess.Popen(
        [sys.executable, _CHILD, str(port), "1", "2", str(tmp_path), "timeout"],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    out, err = p.communicate(timeout=90)
    elapsed = time.monotonic() - t0
    if p.returncode == 0:  # future jax: initialize raises cleanly and Runtime wraps it
        res = json.loads(out.strip().splitlines()[-1])
        assert res["raised"], "Runtime must raise when the coordinator is absent"
        assert "multihost" in res["msg"]
    else:
        assert "DEADLINE_EXCEEDED" in err or "Deadline Exceeded" in err, f"unexpected failure:\n{err}"
    assert elapsed < 60, f"coordinator-absent boot took {elapsed:.0f}s — timeout not applied"


@pytest.mark.timeout(300)
def test_mismatched_device_counts_rejected(tmp_path):
    """Processes with different local device counts must fail fast with a clear
    error (DP meshes need equal per-rank shards), not die later in sharding."""
    by_pid, _ = _run_children(
        _free_port(), 2, tmp_path, "mismatch", extra_args={0: ["2"], 1: ["4"]}
    )
    for pid in (0, 1):
        assert by_pid[pid]["raised"], f"process {pid} accepted a heterogeneous pod"
        assert "Heterogeneous local device counts" in by_pid[pid]["msg"]


@pytest.mark.timeout(600)
def test_crosshost_decoupled_ppo_step(tmp_path):
    """A full decoupled PPO round across 2 processes: global device 0 plays,
    the other 3 devices form the cross-process trainer mesh. Asserts the real
    jitted PPO optimization ran (params changed), stayed bit-identical across
    processes (the XLA allreduce), and the player refresh matches exactly."""
    child = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "decoupled_child.py")
    by_pid, _ = _run_children(_free_port(), 2, tmp_path, timeout=540, child=child)
    for pid in (0, 1):
        assert by_pid[pid]["changed"], "optimization must actually update params"
        assert by_pid[pid]["player_matches"]
    assert by_pid[0]["head"] == by_pid[1]["head"], "post-update params must agree bit-for-bit"
    assert by_pid[0]["digest"] == by_pid[1]["digest"]
    assert "id=0" in by_pid[0]["player_device"]  # refresh landed on the player chip


@pytest.mark.timeout(600)
def test_crosshost_decoupled_ppo_cli(tmp_path):
    """The reference's flagship distributed mode through the REAL CLI: a
    2-process `exp=ppo_decoupled fabric.multihost=True` launch must train
    end-to-end over the cross-process trainer mesh and write the final
    checkpoint (reference multi-node launch, ppo_decoupled.py:623-670)."""
    child = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "decoupled_cli_child.py")
    by_pid, _ = _run_children(_free_port(), 2, tmp_path, "ppo_decoupled", timeout=540, child=child)
    for pid in (0, 1):
        assert by_pid[pid]["done"]
    assert by_pid[0]["n_ckpts"] >= 1, "the player process must write the final checkpoint"


@pytest.mark.timeout(600)
def test_crosshost_decoupled_sac_cli(tmp_path):
    """Same as above for `exp=sac_decoupled`: player owns the replay buffer and
    samples, trainer processes join on spec-shaped zero templates (reference
    sac_decoupled.py:548-588)."""
    child = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "decoupled_cli_child.py")
    by_pid, _ = _run_children(_free_port(), 2, tmp_path, "sac_decoupled", timeout=540, child=child)
    for pid in (0, 1):
        assert by_pid[pid]["done"]
    assert by_pid[0]["n_ckpts"] >= 1, "the player process must write the final checkpoint"


@pytest.mark.timeout(300)
def test_resume_under_multihost(tmp_path):
    """Write-once checkpoint -> every process reloads identical state, and the
    resumed run's log dir version-bumps consistently on all processes."""
    by_pid, _ = _run_children(_free_port(), 2, tmp_path, "resume")
    for pid in (0, 1):
        assert by_pid[pid]["iter_num"] == 123
        np.testing.assert_array_equal(
            np.asarray(by_pid[pid]["loaded"]), np.asarray(by_pid[pid]["expected"])
        )
        assert "version_0" in by_pid[pid]["log_dir_1"]
        assert "version_1" in by_pid[pid]["log_dir_2"]
    assert by_pid[0]["log_dir_2"] == by_pid[1]["log_dir_2"]
