"""Supervisor for the fused population backend: ONE trainee, whole fleet.

``population.backend=fused`` collapses the N-trial subprocess fleet into a
single supervised child — :mod:`sheeprl_tpu.orchestrate.fused_trainee` — that
hosts the entire vmapped population in one compiled program. This controller
keeps the orchestrate supervision contract around it:

- the trainee runs under the same READY/FLAG preemption-guard file protocol
  every trial child uses, so SIGTERM drains (emergency state, clean exit 0)
  and a real preemption is distinguishable from completion;
- exits are classified with the same precedence as
  :class:`~sheeprl_tpu.orchestrate.controller.PopulationController`
  (controller kill intent > preemption flag > returncode), and crash exits
  are restarted up to ``population.max_failures`` times;
- the trainee's own journal surface (``population/fitness.jsonl``,
  ``lineage.jsonl``, certified per-member checkpoint slices) lives under the
  shared ``--state-dir`` layout.

The XLA device count for a multi-device population mesh must be forced
BEFORE jax initializes in the child, so the supervisor owns the
``xla_force_host_platform_device_count`` flag (``population.devices``).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from sheeprl_tpu.core.resilience import FLAG_FILE_ENV_VAR, READY_FILE_ENV_VAR, PreemptionGuard
from sheeprl_tpu.orchestrate import resolve
from sheeprl_tpu.orchestrate.fused_trainee import RESULT_TAG

READY_FILENAME = ".guard_ready"
FLAG_FILENAME = ".preempt_flag"


class FusedPopulationController:
    """Spawn/supervise/restart the single fused-population trainee."""

    def __init__(self, spec_path: str, state_dir: str, cfg: Any = None):
        self.spec_path = os.path.abspath(spec_path)
        self.state_dir = os.path.abspath(state_dir)
        os.makedirs(self.state_dir, exist_ok=True)
        self.pcfg = resolve(cfg).population
        self._proc: Optional[subprocess.Popen] = None
        self._log_f: Any = None
        self._intent: Optional[str] = None
        self.failures = 0
        self.incarnations = 0
        self.result: Optional[Dict[str, Any]] = None
        self.guard: Optional[PreemptionGuard] = None

    # -- paths ----------------------------------------------------------------- #

    def _ready_file(self) -> str:
        return os.path.join(self.state_dir, READY_FILENAME)

    def _flag_file(self) -> str:
        return os.path.join(self.state_dir, FLAG_FILENAME)

    def _log(self, msg: str) -> None:
        print(f"[orchestrate.fused] {msg}", flush=True)

    # -- child lifecycle -------------------------------------------------------- #

    def _spawn(self, max_runtime_s: Optional[float]) -> None:
        for path in (self._ready_file(), self._flag_file()):
            try:
                os.remove(path)
            except OSError:
                pass
        self.incarnations += 1
        log_path = os.path.join(self.state_dir, f"trainee_inc{self.incarnations:02d}.log")
        self._log_f = open(log_path, "ab")
        # JAX_PLATFORMS passes through as the launcher's own environment has it
        # (unset stays unset): the trainee land on the backend the operator chose,
        # never on a CPU nobody asked for
        env = dict(
            os.environ,
            **{
                READY_FILE_ENV_VAR: self._ready_file(),
                FLAG_FILE_ENV_VAR: self._flag_file(),
            },
        )
        devices = int(self.pcfg.devices)
        if devices > 1:
            flags = env.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                env["XLA_FLAGS"] = (
                    f"{flags} --xla_force_host_platform_device_count={devices}".strip()
                )
        argv = [
            sys.executable,
            "-m",
            "sheeprl_tpu.orchestrate.fused_trainee",
            "--spec",
            self.spec_path,
            "--state-dir",
            self.state_dir,
        ]
        if max_runtime_s is not None:
            argv += ["--max-runtime-s", str(max_runtime_s)]
        self._proc = subprocess.Popen(
            argv, env=env, stdout=subprocess.PIPE, stderr=self._log_f, text=True
        )
        if self.guard is not None:
            self.guard.register_child(self._proc.pid)
        self._log(
            f"spawned fused trainee inc={self.incarnations} pid={self._proc.pid} "
            f"members={self.pcfg.members} devices={devices}"
        )

    def _reap(self) -> int:
        assert self._proc is not None
        out, _ = self._proc.communicate()
        rc = self._proc.returncode
        if self.guard is not None:
            self.guard.unregister_child(self._proc.pid)
        for line in (out or "").splitlines():
            if line.startswith(RESULT_TAG):
                try:
                    self.result = json.loads(line[len(RESULT_TAG) :])
                except json.JSONDecodeError:
                    pass
            else:
                print(line, flush=True)
        if self._log_f is not None:
            try:
                self._log_f.close()
            except OSError:
                pass
        self._proc = None
        return rc

    # -- main loop --------------------------------------------------------------- #

    def run(self, max_runtime_s: Optional[float] = None) -> str:
        start = time.time()
        max_failures = int(self.pcfg.max_failures)
        with PreemptionGuard(enabled=True, forward_to_children=True) as guard:
            self.guard = guard
            while True:
                budget = None
                if max_runtime_s is not None:
                    budget = max(max_runtime_s - (time.time() - start), 1.0)
                self._spawn(budget)
                while self._proc.poll() is None:
                    if guard.should_stop and self._intent is None:
                        # the guard already forwarded the signal; remember why
                        self._intent = "preempt"
                    if (
                        max_runtime_s is not None
                        and time.time() - start > max_runtime_s
                        and self._intent is None
                    ):
                        self._intent = "timeout"
                        try:
                            self._proc.send_signal(signal.SIGTERM)
                        except (ProcessLookupError, OSError):
                            pass
                    time.sleep(0.1)
                rc = self._reap()
                intent, self._intent = self._intent, None
                flagged = os.path.exists(self._flag_file())
                if intent == "preempt" or (flagged and intent is None):
                    self._log(f"trainee preempted (rc={rc})")
                    return "preempted"
                if intent == "timeout":
                    self._log(f"trainee stopped at the runtime budget (rc={rc})")
                    return "timeout"
                if rc == 0:
                    self._log("trainee completed")
                    return "done"
                self.failures += 1
                self._log(f"trainee crashed (rc={rc}), failures={self.failures}/{max_failures}")
                if self.failures > max_failures:
                    return "failed"

    def summary(self, status: str) -> Dict[str, Any]:
        return {
            "status": status,
            "backend": "fused",
            "incarnations": self.incarnations,
            "failures": self.failures,
            "trainee": self.result or {},
        }
