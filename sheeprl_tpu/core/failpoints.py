"""Deterministic fault injection: named failpoints with triggers and actions.

Every hardening seam in the runtime (checkpoint fsync, KV transport send/recv,
hot-reload canary, orchestrator injection, env workers, the in-graph vector-env
driver's ``env.reset``/``env.autoreset``, preemption guard) hosts a named
hook::

    from sheeprl_tpu.core import failpoints
    failpoints.failpoint("ckpt.finalize", path=final_path)

Hooks are **zero-cost no-ops unless activated**: the fast path is a single
module-global ``is None`` check — no registry lookup, no string hashing, no
allocation — so production binaries pay nothing for carrying the seams
(guarded by ``tests/test_core/test_failpoints.py``).

Activation comes from the ``SHEEPRL_TPU_FAILPOINTS`` environment variable (read
once at import, so subprocess drills inherit faults through their env) or
programmatically via :func:`configure` / the :func:`active` context manager.

Spec grammar (comma-separated entries)::

    name:action[:arg][:trigger]

    ckpt.finalize:corrupt                     # corrupt the file, every hit
    preempt.iteration:signal:SIGTERM:hit=3    # self-SIGTERM on the 3rd hit
    control.kv_set:drop::every=4              # drop every 4th KV write
    control.kv_set:drop::prob=0.1;seed=7      # seeded 10% drop rate

The trigger field is the one containing ``=``; triggers are deterministic:

``hit=N``
    fire only on the Nth evaluation of the failpoint (1-based).
``every=N``
    fire on every Nth evaluation.
``prob=P;seed=S``
    fire with probability P from a dedicated ``random.Random(S)`` stream —
    reproducible for a fixed seed and hit sequence (default seed 0).

Actions (``arg`` in parentheses):

``raise(msg)``      raise :class:`FailpointError`.
``sleep(seconds)``  block the caller; models a network/disk stall.
``hang(seconds)``   sleep, default 3600 s — rely on the caller's deadline.
``kill(rc)``        ``os._exit(rc)`` (default 137): a crash, no cleanup.
``signal(SIGTERM)`` deliver a signal to this process: a survivable preemption.
``truncate(frac)``  torn write: truncate ctx ``path``/``file`` to ``frac`` of
                    its current size (default 0.5).
``corrupt(n)``      flip ``n`` bytes (default 1): returns a corrupted copy of
                    ctx ``value`` (str/bytes), or corrupts ctx ``path`` on disk
                    in place, preserving its mtime.
``drop()``          return the :data:`DROPPED` sentinel; the call site skips
                    the operation (a silently lost message).
``fire()``          return ``True``: a pure deterministic go-signal for call
                    sites that branch on it (e.g. orchestrator drill injection).
"""

from __future__ import annotations

import os
import random
import signal as _signal_mod
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

ENV_VAR = "SHEEPRL_TPU_FAILPOINTS"

#: Sentinel returned by the ``drop`` action: the call site should skip the
#: guarded operation (simulated message loss).
DROPPED = object()


class FailpointError(RuntimeError):
    """Raised by the ``raise`` action. Deliberately a RuntimeError subclass so
    generic hardening code (retry loops, canary except blocks) treats it like
    any other operational failure."""


class FailpointSpecError(ValueError):
    """Malformed ``SHEEPRL_TPU_FAILPOINTS`` entry."""


_ACTIONS = ("raise", "sleep", "hang", "kill", "signal", "truncate", "corrupt", "drop", "fire")

# --------------------------------------------------------------------------- #
# Canonical failpoint registry
# --------------------------------------------------------------------------- #
# Every failpoint() call site in the tree, keyed by name, with the plane that
# owns it and what firing there simulates. This is DOCUMENTATION + DRIFT
# PROTECTION, not an allowlist: failpoint()/configure() accept any name (unit
# tests mint throwaway ones), but spec_entry() below and the SA005 rule in
# sheeprl_tpu.analysis (which reads this dict statically) both resolve names
# against it, so a typo'd drill fails loudly instead of silently injecting
# nothing. Keep the literal dict parseable by ast: str keys, dict values.
KNOWN_FAILPOINTS: Dict[str, Dict[str, str]] = {
    "ckpt.pre_fsync": {"plane": "checkpoint", "doc": "crash before the manifest fsync (torn write)"},
    "ckpt.finalize": {"plane": "checkpoint", "doc": "crash between payload write and manifest rename"},
    "ckpt.load": {"plane": "checkpoint", "doc": "corrupt/failed restore on the resume path"},
    "ckpt.shard_write": {"plane": "checkpoint", "doc": "host dies/tears its shard before the shard fsync"},
    "ckpt.commit": {"plane": "checkpoint", "doc": "host dies between the commit barrier and the marker rename"},
    "ckpt.replicate": {"plane": "checkpoint", "doc": "peer-RAM replication push dropped/failed"},
    "transport.kv_set": {"plane": "transport", "doc": "weight-push KV write fails"},
    "transport.kv_get": {"plane": "transport", "doc": "weight-pull KV read fails"},
    "transport.player_crash": {"plane": "transport", "doc": "player process dies mid-stream"},
    "control.kv_set": {"plane": "control", "doc": "control-plane KV write fails"},
    "control.kv_get": {"plane": "control", "doc": "control-plane KV read fails"},
    "control.chunk_send": {"plane": "control", "doc": "outbound control chunk dropped/corrupted"},
    "control.chunk_recv": {"plane": "control", "doc": "inbound control chunk dropped/corrupted"},
    "reload.canary": {"plane": "serve", "doc": "canary model fails during a hot reload"},
    "fleet.spawn": {"plane": "serve", "doc": "serve replica spawn fails at process start"},
    "fleet.heartbeat": {"plane": "serve", "doc": "supervisor heartbeat probe of a replica disrupted"},
    "fleet.deploy": {"plane": "serve", "doc": "rolling-deploy canary fails on the first replica"},
    "router.dial": {"plane": "serve", "doc": "router connect to a backend replica fails"},
    "router.relay": {"plane": "serve", "doc": "router relay to a replica dies mid-flight"},
    "orchestrate.journal": {"plane": "orchestrate", "doc": "journal append fails (torn orchestrator state)"},
    "orchestrate.spawn": {"plane": "orchestrate", "doc": "member spawn fails at process start"},
    "orchestrate.inject": {"plane": "orchestrate", "doc": "periodic orchestrator-driven member fault"},
    "population.exploit": {"plane": "orchestrate", "doc": "in-graph PBT exploit step fails at an epoch boundary"},
    "population.member_sync": {"plane": "orchestrate", "doc": "per-member checkpoint-slice sync fails (fire: poison the member's params)"},
    "env.step": {"plane": "env", "doc": "environment step raises/hangs"},
    "env.reset": {"plane": "env", "doc": "environment reset raises/hangs"},
    "env.autoreset": {"plane": "env", "doc": "autoreset path misbehaves after episode end"},
    "preempt.iteration": {"plane": "train", "doc": "preemption signal at a training-iteration boundary"},
    "train.fused_update": {"plane": "train", "doc": "fused in-graph update step fails"},
    "handoff.shard_put": {"plane": "train", "doc": "per-shard rollout handoff put fails mid-iteration (parallel/handoff.py)"},
    "train.grad_sync": {"plane": "train", "doc": "microbatched gradient-sync train dispatch fails at an iteration boundary"},
    "telemetry.program_record": {"plane": "telemetry", "doc": "compiled-program ledger capture fails"},
    "bench.ledger_append": {"plane": "telemetry", "doc": "bench record append to the persistent ledger fails"},
}


def register(name: str, plane: str, doc: str = "") -> None:
    """Add a failpoint to the canonical registry at runtime (plugins/tests that
    ship their own sites and still want spec_entry() validation)."""
    KNOWN_FAILPOINTS[name] = {"plane": plane, "doc": doc}


def known() -> Dict[str, Dict[str, str]]:
    """Snapshot of the canonical registry (name -> {plane, doc})."""
    return {k: dict(v) for k, v in KNOWN_FAILPOINTS.items()}


def spec_entry(name: str, action: str, arg: str = "", trigger: str = "") -> str:
    """Build one validated ``SHEEPRL_TPU_FAILPOINTS`` entry.

    Drills that assemble spec strings by hand get no spelling protection —
    an unknown name configures a failpoint nobody evaluates and the drill
    "passes" without injecting anything. This helper fails fast instead::

        spec = ",".join([
            failpoints.spec_entry("control.chunk_send", "drop", trigger="every=3"),
            failpoints.spec_entry("transport.player_crash", "kill", "9", "hit=2"),
        ])
    """
    if name not in KNOWN_FAILPOINTS:
        raise FailpointSpecError(
            f"unknown failpoint name {name!r}; known: {', '.join(sorted(KNOWN_FAILPOINTS))} "
            "(register() it first for custom sites)"
        )
    if action not in _ACTIONS:
        raise FailpointSpecError(
            f"unknown failpoint action {action!r}; known: {', '.join(_ACTIONS)}"
        )
    fields = [name, action]
    if arg:
        fields.append(arg)
    if trigger:
        fields.append(trigger)
    return ":".join(fields)


@dataclass
class _Spec:
    name: str
    action: str
    arg: str = ""
    trigger: str = "always"  # always | hit | every | prob
    trigger_n: int = 0
    trigger_p: float = 0.0
    rng: Optional[random.Random] = None
    hits: int = 0
    fires: int = 0
    extras: Dict[str, str] = field(default_factory=dict)
    # telemetry trace id active at the most recent hit ("" while tracing is
    # off): ties a drill's injected fault to the exact trace that tripped it
    last_trace_id: str = ""


# None <=> disabled: failpoint() must do NOTHING beyond this identity check.
_active: Optional[Dict[str, _Spec]] = None
_lock = threading.Lock()


def failpoint(name: str, **ctx: Any) -> Any:
    """Evaluate the named failpoint. Returns ``None`` when disabled or not
    triggered; otherwise the action's result (see module docstring)."""
    if _active is None:  # the entire production cost of a failpoint
        return None
    return _fire(name, ctx)


def _fire(name: str, ctx: Dict[str, Any]) -> Any:
    with _lock:
        spec = _active.get(name) if _active is not None else None
        if spec is None:
            return None
        spec.hits += 1
        spec.last_trace_id = _trace_id()
        triggered = _should_trigger(spec)
        if triggered:
            spec.fires += 1
    if not triggered:
        return None
    # a fired failpoint is an event worth correlating: mark it in the active
    # trace BEFORE the action runs (kill/raise actions never return here)
    try:
        from sheeprl_tpu.telemetry import trace as _trace

        _trace.instant(f"failpoint/{name}", action=spec.action, hit=spec.hits)
    except Exception:
        pass
    return _run_action(spec, ctx)


def _trace_id() -> str:
    try:
        from sheeprl_tpu.telemetry import trace as _trace

        return _trace.current_trace_id()
    except Exception:
        return ""


def _should_trigger(spec: _Spec) -> bool:
    if spec.trigger == "always":
        return True
    if spec.trigger == "hit":
        return spec.hits == spec.trigger_n
    if spec.trigger == "every":
        return spec.trigger_n > 0 and spec.hits % spec.trigger_n == 0
    if spec.trigger == "prob":
        return spec.rng.random() < spec.trigger_p
    return False


# --------------------------------------------------------------------------- #
# actions
# --------------------------------------------------------------------------- #


def _run_action(spec: _Spec, ctx: Dict[str, Any]) -> Any:
    if spec.action == "raise":
        raise FailpointError(spec.arg or f"failpoint {spec.name} fired (hit {spec.hits})")
    if spec.action == "sleep":
        time.sleep(float(spec.arg or 0.1))
        return True
    if spec.action == "hang":
        time.sleep(float(spec.arg or 3600.0))
        return True
    if spec.action == "kill":
        os._exit(int(spec.arg or 137))
    if spec.action == "signal":
        os.kill(os.getpid(), _resolve_signal(spec.arg or "SIGTERM"))
        return True
    if spec.action == "truncate":
        return _truncate(spec, ctx)
    if spec.action == "corrupt":
        return _corrupt(spec, ctx)
    if spec.action == "drop":
        return DROPPED
    if spec.action == "fire":
        return True
    raise FailpointSpecError(f"unknown failpoint action {spec.action!r}")


def _resolve_signal(name: str) -> int:
    if name.isdigit():
        return int(name)
    return int(getattr(_signal_mod, name if name.startswith("SIG") else "SIG" + name))


def _truncate(spec: _Spec, ctx: Dict[str, Any]) -> Any:
    frac = float(spec.arg or 0.5)
    fobj = ctx.get("file")
    if fobj is not None:
        fobj.flush()
        size = os.fstat(fobj.fileno()).st_size
        fobj.truncate(max(0, int(size * frac)))
        return True
    path = ctx.get("path")
    if path is None:
        raise FailpointSpecError(f"failpoint {spec.name}: truncate needs a 'file' or 'path' ctx")
    size = os.path.getsize(path)
    st = os.stat(path)
    with open(path, "r+b") as f:
        f.truncate(max(0, int(size * frac)))
    os.utime(path, (st.st_atime, st.st_mtime))
    return True


def _flip(raw: bytearray, nbytes: int) -> None:
    # deterministic positions: spread flips around the middle of the payload,
    # inside any CRC-covered region and away from headers/footers
    for i in range(nbytes):
        raw[(len(raw) // 2 + i) % max(1, len(raw))] ^= 0xFF


def _corrupt(spec: _Spec, ctx: Dict[str, Any]) -> Any:
    nbytes = int(spec.arg or 1)
    value = ctx.get("value")
    if value is not None:
        if isinstance(value, str):
            raw = bytearray(value.encode("utf-8", errors="surrogateescape"))
            _flip(raw, nbytes)
            return raw.decode("utf-8", errors="surrogateescape")
        raw = bytearray(value)
        _flip(raw, nbytes)
        return bytes(raw)
    path = ctx.get("path")
    if path is None:
        raise FailpointSpecError(f"failpoint {spec.name}: corrupt needs a 'value' or 'path' ctx")
    st = os.stat(path)
    with open(path, "r+b") as f:
        raw = bytearray(f.read())
        _flip(raw, nbytes)
        f.seek(0)
        f.write(bytes(raw))
    os.utime(path, (st.st_atime, st.st_mtime))  # bit rot does not touch mtime
    return True


# --------------------------------------------------------------------------- #
# configuration
# --------------------------------------------------------------------------- #


def _parse_entry(entry: str) -> _Spec:
    fields = entry.strip().split(":")
    if len(fields) < 2 or not fields[0]:
        raise FailpointSpecError(f"failpoint entry {entry!r}: want name:action[:arg][:trigger]")
    name, action = fields[0], fields[1]
    if action not in _ACTIONS:
        raise FailpointSpecError(f"failpoint entry {entry!r}: unknown action {action!r}")
    arg, trigger_field = "", ""
    for f in fields[2:]:
        if "=" in f:
            trigger_field = f
        elif f:
            arg = f
    spec = _Spec(name=name, action=action, arg=arg)
    if trigger_field:
        parts = dict(p.split("=", 1) for p in trigger_field.split(";") if "=" in p)
        if "hit" in parts:
            spec.trigger, spec.trigger_n = "hit", int(parts["hit"])
        elif "every" in parts:
            spec.trigger, spec.trigger_n = "every", int(parts["every"])
        elif "prob" in parts:
            spec.trigger = "prob"
            spec.trigger_p = float(parts["prob"])
            spec.rng = random.Random(int(parts.get("seed", 0)))
        else:
            raise FailpointSpecError(f"failpoint entry {entry!r}: unknown trigger {trigger_field!r}")
        spec.extras = parts
    return spec


def configure(spec: Optional[str]) -> None:
    """(Re)activate failpoints from a spec string; ``None``/empty disables."""
    global _active
    if not spec:
        with _lock:
            _active = None
        return
    parsed = {}
    for entry in spec.split(","):
        if not entry.strip():
            continue
        s = _parse_entry(entry)
        parsed[s.name] = s
    with _lock:
        _active = parsed or None


def configure_from_env(environ: Optional[Dict[str, str]] = None) -> None:
    configure((environ if environ is not None else os.environ).get(ENV_VAR))


def reset() -> None:
    """Disable all failpoints and forget their counters."""
    configure(None)


def enabled() -> bool:
    return _active is not None


def has(name: str) -> bool:
    """Is a spec registered for ``name``? (Cheap; for call sites that switch
    between a legacy timing-based path and a failpoint-driven one.)"""
    a = _active
    return a is not None and name in a


def counts() -> Dict[str, Dict[str, Any]]:
    """Per-failpoint ``{"hits": .., "fires": .., "last_trace_id": ..}`` — for
    drill assertions and fault<->trace correlation."""
    with _lock:
        a = _active or {}
        return {
            name: {"hits": s.hits, "fires": s.fires, "last_trace_id": s.last_trace_id}
            for name, s in a.items()
        }


class active:
    """Context manager scoping a failpoint configuration to a block (tests)."""

    def __init__(self, spec: str):
        self.spec = spec
        self._prev: Optional[Dict[str, _Spec]] = None

    def __enter__(self) -> "active":
        global _active
        with _lock:
            self._prev = _active
        configure(self.spec)
        return self

    def __exit__(self, *exc: Any) -> None:
        global _active
        with _lock:
            _active = self._prev


# Subprocess drills set SHEEPRL_TPU_FAILPOINTS in the child env; reading it at
# import means every entry point (sheeprl.py, serve, orchestrate, bench
# children) inherits its faults with no plumbing.
configure_from_env()
