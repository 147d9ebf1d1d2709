"""Replica-fleet supervisor: N serve subprocesses behind one failover router.

Composes the subsystems earlier rounds built into the fleet layer ROADMAP
item 4 names:

- **Supervision** (`orchestrate/`-style): each replica is a slot. Spawn goes
  through a ready-file handshake (the replica's own ``serve.server.ready_file``
  contract), exits are classified with the orchestrator's precedence — kill
  intent (supervisor-initiated drain/deploy) > preemption flag file (the
  replica's :class:`~sheeprl_tpu.core.resilience.PreemptionGuard` wrote it on
  SIGTERM) > returncode — and unexpected exits are respawned under a budgeted
  :func:`~sheeprl_tpu.core.resilience.jittered_backoff` schedule.
- **Liveness** (control-plane primitives): the supervisor runs an in-process
  :class:`~sheeprl_tpu.parallel.control.KVServer` and one
  :class:`~sheeprl_tpu.parallel.control.ControlPlane` per slot. A successful
  health probe of a replica beats that slot's heartbeat key;
  ``peer_liveness`` then gives staleness-based liveness, so a wedged replica
  (process alive, frontend dead) is killed and respawned, not just mourned.
- **Epoch fencing**: every (re)spawn bumps the slot's fenced session epoch via
  ``ControlPlane.begin_session`` — the same primitive that fences zombie
  trainers — and the epoch is stamped into the membership file the router
  consumes. A stale incarnation (or a forged membership write) carries a
  lower epoch than the slot's high-water mark and the router refuses to route
  to it: a fenced zombie replica never answers anything.
- **Rolling certified deploys**: the supervisor (not the replicas — they run
  with hot-reload disabled) watches ``latest_certified`` over the checkpoint
  dir. A new certified artifact is deployed one replica at a time: drain the
  slot out of the membership, SIGTERM it (zero-loss drain), respawn on the new
  checkpoint, wait ready. The FIRST replica is the canary — the
  ``fleet.deploy`` failpoint plus a post-boot health verification gate the
  rest of the fleet, and a canary failure rolls the slot back to the previous
  artifact fleet-wide (``Fleet/deploy_rollbacks``).

``python -m sheeprl_tpu.serve.fleet checkpoint_path=<ckpt> ...`` runs the
supervisor + router until SIGTERM, with
``PreemptionGuard(forward_to_children=True)`` fanning the signal out so every
replica drains itself to rc 0 — the fleet-wide version of the single-server
shutdown contract: every request that ever reached the fleet gets exactly one
answer.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from sheeprl_tpu.core import failpoints
from sheeprl_tpu.core.health import append_event
from sheeprl_tpu.core.resilience import (
    FLAG_FILE_ENV_VAR,
    PreemptionGuard,
    jittered_backoff,
)
from sheeprl_tpu.parallel.control import ControlPlane, KVServer, SocketKV
from sheeprl_tpu.serve.router import FailoverRouter
from sheeprl_tpu.serve.stats import FleetStats
from sheeprl_tpu.telemetry import registry as tel_registry
from sheeprl_tpu.telemetry import trace
from sheeprl_tpu.utils.checkpoint import artifact_bootable, certified_info, latest_certified

_logger = logging.getLogger(__name__)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# tests point replicas at a stub entry the same way orchestrate tests do
ENTRY_ENV_VAR = "SHEEPRL_TPU_SERVE_ENTRY"


def _entry_point() -> str:
    return os.environ.get(ENTRY_ENV_VAR) or os.path.join(REPO_ROOT, "sheeprl_serve.py")


def _rpc(addr: Tuple[str, int], payload: Dict[str, Any], timeout: float = 5.0) -> Dict[str, Any]:
    with socket.create_connection(addr, timeout=timeout) as sock:
        f = sock.makefile("rwb")
        f.write((json.dumps(payload) + "\n").encode())
        f.flush()
        line = f.readline()
    if not line:
        raise ConnectionError("replica closed connection")
    return json.loads(line)


class ReplicaHandle:
    """One slot's current incarnation (process, epoch, handshake paths)."""

    def __init__(self, slot: int, epoch: int, ckpt: str, step: Optional[int], workdir: str):
        self.slot = slot
        self.epoch = epoch
        self.ckpt = ckpt
        self.step = step
        self.dir = os.path.join(workdir, f"replica{slot}")
        tag = f"e{epoch}"
        self.ready_file = os.path.join(self.dir, f"ready_{tag}.json")
        self.flag_file = os.path.join(self.dir, f"preempt_{tag}.flag")
        self.stats_file = os.path.join(self.dir, f"stats_{tag}.json")
        self.log_file = os.path.join(self.dir, "replica.log")
        self.proc: Optional[subprocess.Popen] = None
        self.log_f: Any = None
        self.addr: Optional[Tuple[str, int]] = None
        self.pid: Optional[int] = None
        self.restarts = 0
        self.heartbeats = 0
        self.spawned_at = 0.0


class FleetSupervisor:
    def __init__(
        self,
        checkpoint_path: str,
        workdir: str,
        *,
        replicas: int = 3,
        serve_overrides: Tuple[str, ...] = (),
        replica_env: Optional[Dict[str, str]] = None,
        heartbeat_s: float = 0.25,
        heartbeat_timeout_s: float = 10.0,
        restart_backoff_s: float = 0.25,
        restart_backoff_max_s: float = 2.0,
        max_restarts: int = 8,
        drain_timeout_s: float = 45.0,
        ready_timeout_s: float = 240.0,
        deploy_poll_s: float = 0.5,
        deploy_retry_s: float = 1.0,
        router_opts: Optional[Dict[str, Any]] = None,
    ):
        self.checkpoint_path = os.path.abspath(checkpoint_path)
        self.ckpt_dir = os.path.dirname(self.checkpoint_path)
        self.workdir = os.path.abspath(workdir)
        os.makedirs(self.workdir, exist_ok=True)
        self.replicas = int(replicas)
        self.serve_overrides = tuple(serve_overrides)
        self.replica_env = dict(replica_env or {})
        self.heartbeat_s = float(heartbeat_s)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.restart_backoff_s = float(restart_backoff_s)
        self.restart_backoff_max_s = float(restart_backoff_max_s)
        self.max_restarts = int(max_restarts)
        self.drain_timeout_s = float(drain_timeout_s)
        self.ready_timeout_s = float(ready_timeout_s)
        self.deploy_poll_s = float(deploy_poll_s)
        self.deploy_retry_s = float(deploy_retry_s)

        self.stats = FleetStats()
        self.events_dir = os.path.join(self.workdir, "health")
        self.membership_file = os.path.join(self.workdir, "membership.json")
        # liveness + epoch fencing ride the existing control plane: one KV
        # server in-process, one plane per slot (rank == slot)
        self._kv = KVServer()
        self._kv.start()
        self._planes = [
            ControlPlane(
                SocketKV(self._kv.address),
                rank=slot,
                world=self.replicas,
                scope="fleet",
                timeout_ms=10_000,
            )
            for slot in range(self.replicas)
        ]
        self.router = FailoverRouter(
            self.membership_file, self.stats, **dict(router_opts or {})
        )
        self._handles: Dict[int, ReplicaHandle] = {}
        self._intents: Dict[int, str] = {}
        self._respawn_at: Dict[int, float] = {}
        self._dead_slots: set = set()
        self._last_membership: Optional[str] = None
        self._last_probe = 0.0
        self._last_deploy_check = 0.0
        self._deploy_retry_at = 0.0
        self._replica_reports: List[Dict[str, Any]] = []
        self.guard: Optional[PreemptionGuard] = None
        info = certified_info(self.checkpoint_path) or {}
        self._current_ckpt = self.checkpoint_path
        self._current_ident: Tuple[Any, Any] = (self.checkpoint_path, info.get("crc32"))
        self._current_step = info.get("policy_step")
        tel_registry.register("fleet", self.stats.snapshot)

    # ----- spawn / handshake ----------------------------------------------------
    def _spawn(self, slot: int, ckpt: str, step: Optional[int]) -> ReplicaHandle:
        # Drill site: `fleet.spawn:raise:...:hit=N` fails a replica launch —
        # the budgeted-backoff respawn path must absorb it.
        failpoints.failpoint("fleet.spawn", slot=slot)
        # the fenced session epoch IS the replica generation stamp: a zombie of
        # the previous incarnation keeps the old epoch and the router fences it
        epoch = self._planes[slot].begin_session(role=f"slot{slot}")
        handle = ReplicaHandle(slot, epoch, ckpt, step, self.workdir)
        os.makedirs(handle.dir, exist_ok=True)
        for path in (handle.ready_file, handle.flag_file):
            try:
                os.remove(path)
            except OSError:
                pass
        cmd = [
            sys.executable,
            _entry_point(),
            f"checkpoint_path={ckpt}",
            f"serve.server.ready_file={handle.ready_file}",
            f"stats_file={handle.stats_file}",
            # the supervisor owns weight changes (rolling deploys); a replica
            # hot-reloading on its own would race the deploy's epoch stamps
            "serve.reload.enabled=false",
            *self.serve_overrides,
        ]
        # JAX_PLATFORMS passes through as the launcher's own environment has it
        # (unset stays unset): replicas land on the backend the operator chose,
        # never on a CPU nobody asked for
        env = dict(
            os.environ,
            **{FLAG_FILE_ENV_VAR: handle.flag_file},
        )
        # the supervisor's own drill failpoints must not leak into replicas;
        # per-replica injection opts in through replica_env
        env.pop("SHEEPRL_TPU_FAILPOINTS", None)
        env.update(self.replica_env)
        handle.log_f = open(handle.log_file, "ab")
        handle.proc = subprocess.Popen(
            cmd, cwd=handle.dir, env=env, stdout=handle.log_f, stderr=subprocess.STDOUT
        )
        handle.pid = handle.proc.pid
        handle.spawned_at = time.monotonic()
        if self.guard is not None:
            self.guard.register_child(handle.pid)
        prev = self._handles.get(slot)
        if prev is not None:
            handle.restarts = prev.restarts
        self._handles[slot] = handle
        trace.instant("fleet/spawn", slot=slot, epoch=epoch, pid=handle.pid)
        append_event(self.events_dir, "fleet_replica_spawn", int(step or 0), slot=slot, epoch=epoch, pid=handle.pid)
        _logger.info("[fleet] spawn slot=%d epoch=%d pid=%d ckpt=%s", slot, epoch, handle.pid, ckpt)
        return handle

    def _wait_ready(self, slots: List[int], timeout: Optional[float] = None) -> None:
        """Block until every slot's replica wrote its ready file (host/port),
        then add them to the membership. A replica dying pre-ready raises."""
        budget = timeout if timeout is not None else self.ready_timeout_s
        deadline = time.monotonic() + budget
        pending = set(slots)
        while pending:
            if time.monotonic() > deadline:
                raise TimeoutError(f"replicas {sorted(pending)} not ready within {budget}s")
            for slot in list(pending):
                h = self._handles[slot]
                if h.proc is not None and h.proc.poll() is not None:
                    tail = ""
                    try:
                        with open(h.log_file) as f:
                            tail = f.read()[-2000:]
                    except OSError:
                        pass
                    raise RuntimeError(
                        f"replica slot={slot} exited rc={h.proc.returncode} before ready; log tail:\n{tail}"
                    )
                if os.path.isfile(h.ready_file):
                    try:
                        with open(h.ready_file) as f:
                            info = json.load(f)
                    except ValueError:
                        continue  # mid-replace; retry
                    h.addr = (info["host"], int(info["port"]))
                    pending.discard(slot)
            time.sleep(0.05)
        self._write_membership()
        self.stats.set_gauge("replicas_live", len(self._live_slots()))

    def _live_slots(self) -> List[int]:
        return sorted(
            s
            for s, h in self._handles.items()
            if h.proc is not None and h.proc.poll() is None and h.addr is not None
        )

    # ----- membership -----------------------------------------------------------
    def _write_membership(self) -> None:
        members = []
        for slot in self._live_slots():
            h = self._handles[slot]
            members.append(
                {
                    "slot": slot,
                    "epoch": h.epoch,
                    "host": h.addr[0],
                    "port": h.addr[1],
                    "pid": h.pid,
                    "ckpt": h.ckpt,
                    "step": h.step,
                }
            )
        doc = json.dumps({"members": members}, sort_keys=True)
        # write ONLY on change: the membership file is the router's (and the
        # chaos drill's) observation surface, and an unconditional rewrite
        # every tick would race the drill's forged-zombie-write window
        if doc == self._last_membership:
            return
        tmp = f"{self.membership_file}.tmp"
        with open(tmp, "w") as f:
            f.write(doc)
        os.replace(tmp, self.membership_file)
        self._last_membership = doc

    def _remove_member(self, slot: int) -> None:
        h = self._handles.get(slot)
        if h is not None:
            h.addr = None
        self._write_membership()
        self.stats.set_gauge("replicas_live", len(self._live_slots()))

    # ----- exit classification ---------------------------------------------------
    def _reap(self, handle: ReplicaHandle, rc: int) -> Dict[str, Any]:
        if self.guard is not None and handle.pid is not None:
            self.guard.unregister_child(handle.pid)
        if handle.log_f is not None:
            try:
                handle.log_f.close()
            except OSError:
                pass
            handle.log_f = None
        report = {
            "slot": handle.slot,
            "epoch": handle.epoch,
            "rc": rc,
            "stats_file": handle.stats_file,
        }
        self._replica_reports.append(report)
        handle.proc = None
        return report

    def _classify_exit(self, handle: ReplicaHandle, rc: int, now: float) -> None:
        slot = handle.slot
        # precedence mirrors orchestrate: supervisor intent > preemption flag
        # (the replica's guard wrote it when an EXTERNAL signal landed) > rc
        intent = self._intents.pop(slot, None)
        if intent is None and self.guard is not None and self.guard.should_stop:
            # the guard already forwarded our own shutdown signal to this
            # replica; its exit is the drain we asked for, not a failure
            intent = "shutdown"
        flagged = os.path.exists(handle.flag_file)
        self._remove_member(slot)
        self._reap(handle, rc)
        if intent in ("deploy", "shutdown"):
            cause = intent  # expected: the supervisor asked for this exit
        elif intent == "liveness":
            cause = "liveness_kill"
            self.stats.inc("replica_kills")
        elif flagged:
            cause = "preempted"
            self.stats.inc("replica_preemptions")
        else:
            cause = "failed"
            self.stats.inc("replica_failures")
        trace.instant("fleet/exit", slot=slot, rc=rc, cause=cause)
        append_event(self.events_dir, "fleet_replica_exit", 0, slot=slot, rc=rc, cause=cause, epoch=handle.epoch)
        _logger.info("[fleet] exit slot=%d rc=%s cause=%s", slot, rc, cause)
        if cause in ("deploy", "shutdown"):
            return
        handle.restarts += 1
        if handle.restarts > self.max_restarts:
            self._dead_slots.add(slot)
            append_event(self.events_dir, "fleet_slot_abandoned", 0, slot=slot, restarts=handle.restarts)
            _logger.warning("[fleet] slot %d exhausted its restart budget (%d)", slot, self.max_restarts)
            return
        delay = jittered_backoff(self.restart_backoff_s, handle.restarts, self.restart_backoff_max_s)
        self._respawn_at[slot] = now + delay

    def _poll_exits(self, now: float) -> None:
        for slot, h in list(self._handles.items()):
            if h.proc is None:
                continue
            rc = h.proc.poll()
            if rc is not None:
                self._classify_exit(h, rc, now)

    def _respawn_due(self, now: float) -> None:
        for slot, at in list(self._respawn_at.items()):
            if now < at or slot in self._dead_slots:
                continue
            del self._respawn_at[slot]
            h = self._handles[slot]
            try:
                self._spawn(slot, h.ckpt, h.step)
                self._wait_ready([slot])
            except (RuntimeError, TimeoutError, OSError) as e:
                _logger.warning("[fleet] respawn of slot %d failed: %s", slot, e)
                nh = self._handles[slot]
                if nh.proc is not None:  # launched but died/never-readied
                    if nh.proc.poll() is None:
                        nh.proc.kill()
                        try:
                            nh.proc.wait(timeout=10.0)
                        except subprocess.TimeoutExpired:
                            pass
                    self._reap(nh, nh.proc.returncode if nh.proc else -1)
                self.stats.inc("replica_failures")
                nh.restarts += 1
                if nh.restarts > self.max_restarts:
                    self._dead_slots.add(slot)
                    append_event(
                        self.events_dir, "fleet_slot_abandoned", 0, slot=slot, restarts=nh.restarts
                    )
                else:
                    self._respawn_at[slot] = time.monotonic() + jittered_backoff(
                        self.restart_backoff_s, nh.restarts, self.restart_backoff_max_s
                    )
                continue
            self.stats.inc("replica_restarts")
            append_event(self.events_dir, "fleet_replica_restart", 0, slot=slot, epoch=self._handles[slot].epoch)

    # ----- heartbeat liveness -----------------------------------------------------
    def _probe_health(self, now: float) -> None:
        if now - self._last_probe < self.heartbeat_s:
            return
        self._last_probe = now
        for slot in self._live_slots():
            h = self._handles[slot]
            try:
                # Drill site: `fleet.heartbeat:raise` makes the probe miss
                # (liveness decays); `fleet.heartbeat:signal:SIGTERM:hit=N`
                # delivers the fan-out drill's preemption at a DETERMINISTIC
                # supervision tick instead of a wall-clock race.
                failpoints.failpoint("fleet.heartbeat", slot=slot)
                health = _rpc(h.addr, {"op": "health"}, timeout=2.0)
            except (OSError, ValueError, ConnectionError, RuntimeError):
                continue  # missed beat; staleness accumulates
            if health.get("live"):
                h.heartbeats += 1
                self._planes[slot].heartbeat({"pid": h.pid, "slot_epoch": h.epoch})
                self.stats.inc("heartbeats")
        # staleness-based liveness over the control-plane heartbeat keys: a
        # wedged replica (process alive, frontend dead) stops beating and gets
        # killed + respawned
        liveness = self._planes[0].peer_liveness(max_age_s=self.heartbeat_timeout_s)
        for slot in self._live_slots():
            h = self._handles[slot]
            if h.heartbeats == 0:
                continue  # never beat yet: the boot grace window
            beat = liveness.get(slot, {})
            if beat.get("alive"):
                continue
            _logger.warning("[fleet] slot %d heartbeat stale (age=%s): killing", slot, beat.get("age_s"))
            self._intents[slot] = "liveness"
            try:
                h.proc.kill()
            except (ProcessLookupError, OSError):
                pass

    # ----- rolling deploys --------------------------------------------------------
    def _redeploy_slot(self, slot: int, ckpt: str, step: Optional[int]) -> ReplicaHandle:
        """Drain one replica out of the fleet and respawn it on ``ckpt``."""
        if self.guard is not None and self.guard.should_stop:
            # a deploy must never outlive the shutdown signal: a replica
            # spawned now would miss the guard's already-forwarded SIGTERM
            raise RuntimeError("fleet is shutting down; aborting the rollout")
        h = self._handles[slot]
        if h.proc is not None and h.proc.poll() is None:
            self._intents[slot] = "deploy"
            self._remove_member(slot)  # router stops routing here first
            time.sleep(max(self.router.membership_poll_s * 2, 0.1))
            h.proc.send_signal(signal.SIGTERM)
            try:
                rc = h.proc.wait(timeout=self.drain_timeout_s)
            except subprocess.TimeoutExpired:
                h.proc.kill()
                rc = h.proc.wait(timeout=10.0)
            self._intents.pop(slot, None)
            self._reap(h, rc)
            if rc != 0:
                raise RuntimeError(f"slot {slot} did not drain cleanly for deploy (rc={rc})")
        new = self._spawn(slot, ckpt, step)
        self._wait_ready([slot])
        return new

    def _rolling_deploy(self, path: str, info: Dict[str, Any]) -> bool:
        step = info.get("policy_step")
        order = self._live_slots()
        if not order:
            return False
        canary = order[0]
        trace.instant("fleet/deploy_start", path=path, canary=canary)
        append_event(self.events_dir, "fleet_deploy_start", int(step or 0), path=path, canary=canary)
        try:
            handle = self._redeploy_slot(canary, path, step)
            # Drill site: `fleet.deploy:raise:...:hit=1` fails the canary
            # verification on a healthy artifact — the whole fleet must stay
            # on the previous generation and the canary slot roll back.
            failpoints.failpoint("fleet.deploy", path=path, slot=canary)
            health = _rpc(handle.addr, {"op": "health"}, timeout=5.0)
            if not health.get("ready"):
                raise RuntimeError(f"canary replica not ready: {health}")
        except Exception as e:
            self.stats.inc("deploy_rollbacks")
            append_event(
                self.events_dir,
                "fleet_deploy_rollback",
                int(step or 0),
                path=path,
                canary=canary,
                error=f"{type(e).__name__}: {e}",
            )
            _logger.warning("[fleet] deploy canary failed (%s); rolling back to %s", e, self._current_ckpt)
            try:
                self._redeploy_slot(canary, self._current_ckpt, self._current_step)
            except Exception:
                _logger.exception("[fleet] canary rollback failed; slot will respawn via budget")
            self._deploy_retry_at = time.monotonic() + self.deploy_retry_s
            return False
        for slot in order[1:]:
            if slot not in self._live_slots():
                continue  # died mid-deploy; its respawn will use the NEW ckpt
            try:
                self._redeploy_slot(slot, path, step)
            except Exception:
                _logger.exception("[fleet] redeploy of slot %d failed; continuing the rollout", slot)
        self._current_ckpt, self._current_ident, self._current_step = (
            path,
            (path, info.get("crc32")),
            step,
        )
        self.stats.inc("deploys")
        append_event(self.events_dir, "fleet_deploy", int(step or 0), path=path)
        _logger.info("[fleet] rolling deploy of %s complete", path)
        return True

    def _check_deploy(self, now: float) -> None:
        if now - self._last_deploy_check < self.deploy_poll_s or now < self._deploy_retry_at:
            return
        self._last_deploy_check = now
        path = latest_certified(self.ckpt_dir)
        if path is None:
            return
        info = certified_info(path)
        if info is None:
            return
        if (path, info.get("crc32")) == self._current_ident:
            return
        # Artifact-compat gate (sidecar format/topology stamp + shard-file
        # presence): never start a rolling deploy onto an artifact the
        # replicas can't boot — e.g. a sharded dir with a missing shard file
        # or a format version from a newer build. Recorded, retried later.
        ok, why = artifact_bootable(path, info)
        if not ok:
            self.stats.inc("deploy_rejected")
            append_event(
                self.events_dir,
                "fleet_deploy_rejected",
                int(info.get("policy_step") or 0),
                path=path,
                reason=why,
            )
            _logger.warning("[fleet] deploy of %s rejected: %s", path, why)
            self._deploy_retry_at = now + self.deploy_retry_s
            return
        self._rolling_deploy(path, info)

    # ----- lifecycle --------------------------------------------------------------
    def start(self) -> "FleetSupervisor":
        for slot in range(self.replicas):
            self._spawn(slot, self._current_ckpt, self._current_step)
        self._wait_ready(list(range(self.replicas)))
        self.router.start()
        self.stats.set_gauge("ready", 1)
        return self

    def tick(self) -> None:
        if self.guard is not None and self.guard.should_stop:
            return  # shutdown owns the fleet now; no respawns/deploys past this
        now = time.monotonic()
        self._poll_exits(now)
        self._respawn_due(now)
        self._probe_health(now)
        self._check_deploy(now)
        self._write_membership()

    def run_until_stopped(self, stats_file: Optional[str] = None, ready_file: Optional[str] = None) -> bool:
        """Supervise until SIGTERM/SIGINT, then drain the whole fleet.

        The guard forwards the signal to every replica the moment it lands, so
        replicas drain their own admitted work concurrently while the router
        stops admitting — the fleet-wide zero-loss shutdown contract."""
        wake = threading.Event()
        with PreemptionGuard(
            enabled=True, forward_to_children=True, on_signal=lambda _s: wake.set()
        ) as guard:
            self.guard = guard
            self.start()
            if ready_file:
                tmp = f"{ready_file}.tmp"
                with open(tmp, "w") as f:
                    json.dump(
                        {"host": self.router.host, "port": self.router.port, "pid": os.getpid()}, f
                    )
                os.replace(tmp, ready_file)
            while not guard.should_stop:
                self.tick()
                wake.wait(min(self.heartbeat_s, 0.25))
            _logger.info("[fleet] %s: draining the fleet", guard.describe())
            return self.shutdown(stats_file=stats_file)

    def shutdown(self, stats_file: Optional[str] = None) -> bool:
        self.stats.set_gauge("ready", 0)
        self.stats.set_gauge("draining", 1)
        router_drained = self.router.drain(timeout=self.drain_timeout_s)
        replica_rcs: Dict[int, int] = {}
        final_ids: set = set()
        for slot, h in sorted(self._handles.items()):
            if h.proc is None:
                continue
            self._intents[slot] = "shutdown"
            # SIGTERM unconditionally: the guard forwarded the external signal
            # to children alive AT THAT MOMENT, but a replica spawned since
            # (mid-deploy race) never saw it; a second SIGTERM to a replica
            # already draining is a no-op in its own guard
            try:
                h.proc.send_signal(signal.SIGTERM)
            except (ProcessLookupError, OSError):
                pass
            try:
                rc = h.proc.wait(timeout=self.drain_timeout_s)
            except subprocess.TimeoutExpired:
                _logger.warning("[fleet] slot %d drain timed out; killing", slot)
                h.proc.kill()
                rc = h.proc.wait(timeout=10.0)
            replica_rcs[slot] = rc
            self._intents.pop(slot, None)
            final_ids.add(id(self._reap(h, rc)))
        self.router.close()
        try:
            self._kv.stop()
        except Exception:
            pass
        # the drain verdict audits only each slot's FINAL incarnation: earlier
        # incarnations (a chaos-killed replica, pre-deploy generations) were
        # already classified at exit time and have no stats file to offer
        replicas = []
        all_drained = router_drained
        for report in self._replica_reports:
            row = dict(report)
            row["final"] = id(report) in final_ids
            try:
                with open(report["stats_file"]) as f:
                    row["stats"] = json.load(f)
            except (OSError, ValueError):
                row["stats"] = None
            replicas.append(row)
        for row in replicas:
            if row["final"] and (row["rc"] != 0 or not (row.get("stats") or {}).get("drained")):
                all_drained = False
        if stats_file:
            payload: Dict[str, Any] = self.stats.snapshot()
            payload["drained"] = all_drained
            payload["replica_rcs"] = {str(k): v for k, v in replica_rcs.items()}
            payload["replicas"] = replicas
            tmp = f"{stats_file}.tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=2)
            os.replace(tmp, stats_file)
        return all_drained


# --------------------------------------------------------------------------- CLI
def main(argv: Optional[List[str]] = None) -> int:
    """``python -m sheeprl_tpu.serve.fleet`` — key=value overrides, same
    grammar as the serve CLI. ``serve.*`` keys pass through to every replica;
    ``fleet.*`` / ``router.*`` keys configure the supervisor and the frontend."""
    import yaml

    args = list(sys.argv[1:] if argv is None else argv)
    kv: Dict[str, Any] = {}
    for ov in args:
        key, _, value = ov.partition("=")
        kv[key.strip()] = yaml.safe_load(value)
    ckpt = kv.pop("checkpoint_path", None)
    if not ckpt:
        print("fleet: checkpoint_path=<certified ckpt> is required", file=sys.stderr)
        return 2
    workdir = kv.pop("workdir", None) or os.path.join(os.getcwd(), "fleet")
    stats_file = kv.pop("stats_file", None)
    ready_file = kv.pop("ready_file", None)

    from sheeprl_tpu.serve import _DEFAULTS

    fleet_cfg = dict(_DEFAULTS["fleet"])
    router_cfg = dict(_DEFAULTS["router"])
    serve_overrides: List[str] = []
    for key, value in kv.items():
        if key.startswith("fleet."):
            name = key[len("fleet."):]
            if name not in fleet_cfg:
                print(f"fleet: unknown knob '{key}'", file=sys.stderr)
                return 2
            fleet_cfg[name] = value
        elif key.startswith("router."):
            name = key[len("router."):]
            if name not in router_cfg:
                print(f"fleet: unknown knob '{key}'", file=sys.stderr)
                return 2
            router_cfg[name] = value
        else:
            serve_overrides.append(f"{key}={value}")

    sup = FleetSupervisor(
        ckpt,
        workdir,
        replicas=int(fleet_cfg["replicas"]),
        serve_overrides=tuple(serve_overrides),
        heartbeat_s=float(fleet_cfg["heartbeat_s"]),
        heartbeat_timeout_s=float(fleet_cfg["heartbeat_timeout_s"]),
        restart_backoff_s=float(fleet_cfg["restart_backoff_s"]),
        restart_backoff_max_s=float(fleet_cfg["restart_backoff_max_s"]),
        max_restarts=int(fleet_cfg["max_restarts"]),
        drain_timeout_s=float(fleet_cfg["drain_timeout_s"]),
        deploy_poll_s=float(fleet_cfg["deploy_poll_s"]),
        deploy_retry_s=float(fleet_cfg["deploy_retry_s"]),
        router_opts={
            "host": str(router_cfg["host"]),
            "port": int(router_cfg["port"]),
            "retry_budget": int(router_cfg["retry_budget"]),
            "retry_backoff_ms": float(router_cfg["retry_backoff_ms"]),
            "membership_poll_s": float(router_cfg["membership_poll_s"]),
            "dial_timeout_s": float(router_cfg["dial_timeout_s"]),
            "default_priority": int(router_cfg["default_priority"]),
            "max_workers": int(router_cfg["max_workers"]),
        },
    )
    drained = sup.run_until_stopped(stats_file=stats_file, ready_file=ready_file)
    return 0 if drained else 1


if __name__ == "__main__":
    sys.exit(main())
