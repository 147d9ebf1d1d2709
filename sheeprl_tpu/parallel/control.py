"""Backend-portable multihost control plane over a key-value store.

The scale path needs a HOST control plane — log-dir broadcast, barriers,
spec/digest exchange, liveness — that works on every backend. Routing those
through device collectives (``multihost_utils.broadcast_one_to_all``) couples
"can the hosts talk" to "can the accelerator run a multi-process program",
which the CPU backend historically could not: the whole multihost test cluster
was untestable off-pod. This module keeps host coordination on the channel the
world already booted on — the coordinator's key-value store — behind a small
:class:`KVStore` interface with two implementations:

- :class:`CoordinatorKV`: the jax distributed runtime client
  (``key_value_set`` / ``blocking_key_value_get`` / ``wait_at_barrier``),
  available whenever ``jax.distributed.initialize`` ran;
- :class:`SocketKV` + :class:`KVServer`: a dependency-free TCP store with the
  same contract, for two-process drills (``scripts/transport_smoke.py``),
  benches, and processes that must coordinate OUTSIDE a jax world — notably a
  restarted incarnation that cannot quickly rejoin the coordinator (the
  coordination service holds the dead task's slot until its heartbeat lease
  expires).

On top of the store, :class:`ControlPlane` provides:

- ``broadcast_str`` / ``barrier`` / ``all_gather_meta`` with deadlines and
  jittered retries (every exhaustion is a diagnostic
  :class:`ControlPlaneTimeoutError` naming the key and the likely-dead peer);
- **session epochs**: each (re)start of a role bumps a fenced epoch key, and
  the chunk transport stamps every payload with its writer's epoch — a zombie
  writer from a pre-preemption incarnation is *rejected and counted*
  (``Resilience/stale_epoch_rejects``) instead of corrupting the handoff,
  and learns of its own death through a ``stale`` ack
  (:class:`StaleEpochError`);
- a heartbeat/liveness surface (``heartbeat`` / ``peer_liveness``) feeding
  ``Resilience/*`` counters and, through them, the HealthSentinel's flight
  recorder;
- an epoch-fenced, CRC-checked, ack/resend **chunk transport**
  (``send_chunk`` / ``recv_chunk``) with at-most-once delivery per sequence
  number and a durable reader cursor, so a restarted writer resumes exactly
  where the reader left off — zero lost, zero duplicated chunks even under
  injected drops, delays, and torn payloads (``scripts/transport_smoke.py``).

Device collectives remain the fast path for BULK data on TPU
(``CrossHostTransport.rollout_to_trainers`` rides ICI/DCN); this plane carries
control-sized strings only.

Module-level imports stay jax-free: the orchestrator and the transport smoke's
children use :class:`SocketKV` without an accelerator runtime in sight.
"""

from __future__ import annotations

import base64
import binascii
import json
import logging
import socket
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

from sheeprl_tpu.core import failpoints
from sheeprl_tpu.core.resilience import jittered_backoff

_logger = logging.getLogger(__name__)

KV_UNAVAILABLE_COUNTER = "Resilience/kv_unavailable"

#: Counters a ControlPlane maintains (callers may pass a shared dict).
COUNTER_KEYS = (
    KV_UNAVAILABLE_COUNTER,
    "Resilience/kv_retries",
    "Resilience/stale_epoch_rejects",
    "Resilience/chunk_resends",
    "Resilience/heartbeats_sent",
    "Resilience/peer_stale_heartbeats",
)


class ControlPlaneError(RuntimeError):
    pass


class ControlPlaneTimeoutError(ControlPlaneError):
    """A control-plane operation exhausted its deadline + retries. The message
    names the key and scope so the dead/wedged SIDE is diagnosable from one
    log line."""


class StaleEpochError(ControlPlaneError):
    """This writer's session epoch has been superseded: a newer incarnation of
    the same role is live. The only correct reaction is to stop writing —
    the zombie's payloads are already being rejected by readers."""


class KVUnavailableError(ControlPlaneError):
    """The coordinator KV store is not available in this process."""


# --------------------------------------------------------------------------- #
# coordinator client probe (the canonical home of the old decoupled._kv_client)
# --------------------------------------------------------------------------- #

_warned_unavailable = False


def coordinator_client():
    """The coordinator's key-value store client (None before
    ``jax.distributed.initialize``).

    jax only exposes the client at a private path; it is imported plainly, so a
    jax that moves it is an ImportError here and not a silently dead feature.
    The import stays inside the function: launcher children use this module's
    :class:`SocketKV` without jax."""
    from jax._src import distributed

    return distributed.global_state.client


def require_coordinator_client(what: str, counters: Optional[Dict[str, int]] = None):
    """``coordinator_client()`` or a diagnosis: warn ONCE per process, bump the
    ``Resilience/kv_unavailable`` counter, and raise :class:`KVUnavailableError`
    with the fix spelled out — instead of the bare ``AttributeError`` a None
    client used to produce at its first method call."""
    global _warned_unavailable
    client = coordinator_client()
    if client is not None:
        return client
    if counters is not None:
        counters[KV_UNAVAILABLE_COUNTER] = counters.get(KV_UNAVAILABLE_COUNTER, 0) + 1
    msg = (
        f"{what} needs the jax coordinator KV store, but this process has none. "
        "Either jax.distributed.initialize() has not run (launch with "
        "fabric.multihost=True under a multi-host launcher, or pass "
        "fabric.coordinator_address explicitly), or this jax build does not "
        "expose the distributed runtime client."
    )
    if not _warned_unavailable:
        _warned_unavailable = True
        _logger.warning("[control] %s", msg)
    raise KVUnavailableError(msg)


# --------------------------------------------------------------------------- #
# KV backends
# --------------------------------------------------------------------------- #


class CoordinatorKV:
    """The jax coordination service's store. ``get`` blocks server-side until
    the key exists or the deadline lapses."""

    def __init__(self, client):
        self._client = client

    def set(self, key: str, value: str) -> None:
        self._client.key_value_set(key, value, allow_overwrite=True)

    def get(self, key: str, timeout_ms: int) -> str:
        return self._client.blocking_key_value_get(key, max(1, int(timeout_ms)))

    def try_get(self, key: str, timeout_ms: int = 50) -> Optional[str]:
        try:
            return self.get(key, timeout_ms)
        except Exception:
            return None

    def delete(self, key: str) -> None:
        try:
            self._client.key_value_delete(key)
        except Exception:
            pass

    def wait_at_barrier(self, name: str, timeout_ms: int) -> None:
        self._client.wait_at_barrier(name, max(1, int(timeout_ms)))


class KVServer(threading.Thread):
    """Line-JSON TCP server with the :class:`CoordinatorKV` contract.

    One request per connection; blocking gets park the connection thread on a
    condition variable. Sized for drills and benches (a handful of clients),
    not production fleets — production runs coordinate through the jax
    coordinator this emulates."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        super().__init__(name="sheeprl-kv-server", daemon=True)
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(64)
        self.host, self.port = self._sock.getsockname()[:2]
        self.address = f"{self.host}:{self.port}"
        self._store: Dict[str, str] = {}
        self._cond = threading.Condition()
        self._stopping = False

    def run(self) -> None:
        while not self._stopping:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve_one, args=(conn,), daemon=True).start()

    def stop(self) -> None:
        self._stopping = True
        try:
            self._sock.close()
        except OSError:
            pass
        with self._cond:
            self._cond.notify_all()

    def _serve_one(self, conn: socket.socket) -> None:
        try:
            with conn, conn.makefile("rwb") as f:
                line = f.readline()
                if not line:
                    return
                req = json.loads(line.decode())
                resp = self._handle(req)
                f.write((json.dumps(resp) + "\n").encode())
                f.flush()
        except (OSError, ValueError):
            pass

    def _handle(self, req: Dict[str, Any]) -> Dict[str, Any]:
        op, key = req.get("op"), req.get("key", "")
        if op == "set":
            with self._cond:
                self._store[key] = str(req.get("value", ""))
                self._cond.notify_all()
            return {"ok": True}
        if op == "get":
            deadline = time.monotonic() + float(req.get("timeout_ms", 1000)) / 1000.0
            with self._cond:
                while key not in self._store and not self._stopping:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return {"ok": False, "error": f"deadline exceeded waiting for '{key}'"}
                    self._cond.wait(min(remaining, 0.25))
                if key in self._store:
                    return {"ok": True, "value": self._store[key]}
            return {"ok": False, "error": "server stopping"}
        if op == "delete":
            with self._cond:
                self._store.pop(key, None)
            return {"ok": True}
        return {"ok": False, "error": f"unknown op {op!r}"}


class SocketKV:
    """Client for :class:`KVServer`: one short-lived connection per operation,
    so it survives the server outliving any number of client restarts."""

    def __init__(self, address: str, connect_timeout_s: float = 5.0):
        host, _, port = address.rpartition(":")
        self._addr = (host or "127.0.0.1", int(port))
        self._connect_timeout_s = float(connect_timeout_s)

    def _rpc(self, req: Dict[str, Any], timeout_s: float) -> Dict[str, Any]:
        with socket.create_connection(self._addr, timeout=self._connect_timeout_s) as conn:
            conn.settimeout(timeout_s + self._connect_timeout_s)
            with conn.makefile("rwb") as f:
                f.write((json.dumps(req) + "\n").encode())
                f.flush()
                line = f.readline()
        if not line:
            raise ControlPlaneError("KV server closed the connection mid-request")
        return json.loads(line.decode())

    def set(self, key: str, value: str) -> None:
        resp = self._rpc({"op": "set", "key": key, "value": value}, 10.0)
        if not resp.get("ok"):
            raise ControlPlaneError(resp.get("error", "KV set failed"))

    def get(self, key: str, timeout_ms: int) -> str:
        resp = self._rpc({"op": "get", "key": key, "timeout_ms": int(timeout_ms)}, timeout_ms / 1000.0)
        if not resp.get("ok"):
            raise ControlPlaneTimeoutError(resp.get("error", f"KV get of '{key}' failed"))
        return resp["value"]

    def try_get(self, key: str, timeout_ms: int = 50) -> Optional[str]:
        try:
            return self.get(key, timeout_ms)
        except Exception:
            return None

    def delete(self, key: str) -> None:
        try:
            self._rpc({"op": "delete", "key": key}, 10.0)
        except Exception:
            pass


# --------------------------------------------------------------------------- #
# control plane
# --------------------------------------------------------------------------- #

# Process-global sequence counters for the module-level helpers (logger
# broadcast, Runtime barrier): every process makes the same sequence of calls
# — the same SPMD assumption the device collectives they replace relied on.
_seq_lock = threading.Lock()
_seqs: Dict[str, int] = {}


def _next_seq(name: str) -> int:
    with _seq_lock:
        _seqs[name] = _seqs.get(name, 0) + 1
        return _seqs[name]


class ControlPlane:
    def __init__(
        self,
        kv: Any,
        *,
        rank: int,
        world: int,
        scope: str = "",
        timeout_ms: int = 60_000,
        retries: int = 2,
        backoff_base_s: float = 0.05,
        backoff_max_s: float = 2.0,
        counters: Optional[Dict[str, int]] = None,
    ):
        self.kv = kv
        self.rank = int(rank)
        self.world = int(world)
        self.scope = str(scope)
        self.timeout_ms = int(timeout_ms)
        self.retries = int(retries)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self.counters: Dict[str, int] = counters if counters is not None else {}
        for k in COUNTER_KEYS:
            self.counters.setdefault(k, 0)
        self._epoch = 0
        self._seen_epoch = 0
        self._fence_role: Optional[str] = None
        self._hb_seq = 0
        self._call_seqs: Dict[str, int] = {}

    # -- keys ------------------------------------------------------------------ #

    def _key(self, *parts: str) -> str:
        return "/".join(["sheeprl_tpu", "control", self.scope or "global", *parts])

    def _seq(self, family: str) -> int:
        self._call_seqs[family] = self._call_seqs.get(family, 0) + 1
        return self._call_seqs[family]

    # -- retry/deadline core ---------------------------------------------------- #

    def _retry(self, op: Callable[[], Any], describe: str, timeout_ms: Optional[int] = None) -> Any:
        deadline = time.monotonic() + (timeout_ms if timeout_ms is not None else self.timeout_ms) / 1000.0
        attempt = 0
        while True:
            try:
                return op()
            except (StaleEpochError, KVUnavailableError):
                raise
            except Exception as e:
                attempt += 1
                self.counters["Resilience/kv_retries"] += 1
                if attempt > self.retries or time.monotonic() >= deadline:
                    raise ControlPlaneTimeoutError(
                        f"control-plane {describe} failed after {attempt} attempt(s) "
                        f"(rank {self.rank}, scope '{self.scope or 'global'}'): the peer that "
                        "should have served it is likely dead, preempted, or wedged before "
                        f"its publish point. Last error: {type(e).__name__}: {e}"
                    ) from e
                delay = jittered_backoff(self.backoff_base_s, attempt, self.backoff_max_s)
                time.sleep(min(delay, max(0.0, deadline - time.monotonic())))

    def _set(self, key: str, value: str, describe: str) -> None:
        fp = failpoints.failpoint("control.kv_set", key=key, value=value)
        if fp is failpoints.DROPPED:
            return  # a silently lost write: the reader's deadline surfaces it
        if isinstance(fp, str):
            value = fp
        self._retry(lambda: self.kv.set(key, value), describe or f"KV set of '{key}'")

    def _get(self, key: str, timeout_ms: int, describe: str) -> str:
        out = self._retry(
            lambda: self.kv.get(key, timeout_ms),
            describe or f"KV get of '{key}'",
            timeout_ms=timeout_ms,
        )
        fp = failpoints.failpoint("control.kv_get", key=key, value=out)
        return fp if isinstance(fp, str) else out

    # -- collectives ------------------------------------------------------------- #

    def broadcast_str(self, name: str, value: Optional[str] = None, timeout_ms: Optional[int] = None) -> str:
        """Rank 0's ``value`` on every rank. Every rank must call, in the same
        order (the per-name sequence number is how repeated broadcasts under
        one name stay matched up)."""
        key = self._key("bcast", name, str(self._seq(f"bcast/{name}")))
        if self.rank == 0:
            if value is None:
                raise ValueError(f"broadcast_str('{name}'): rank 0 must provide the value")
            self._set(key, value, f"broadcast of '{name}'")
            return value
        return self._get(
            key,
            timeout_ms if timeout_ms is not None else self.timeout_ms,
            f"broadcast of '{name}' from rank 0",
        )

    def barrier(self, name: str = "barrier", timeout_ms: Optional[int] = None) -> None:
        """All ``world`` ranks rendezvous. Uses the coordinator's native
        barrier when the store has one; otherwise an arrival-counting KV
        barrier (each rank publishes its arrival, then waits for all)."""
        budget = timeout_ms if timeout_ms is not None else self.timeout_ms
        tag = f"{name}/{self._seq(f'barrier/{name}')}"
        native = getattr(self.kv, "wait_at_barrier", None)
        if native is not None:
            self._retry(
                lambda: native(self._key("barrier", tag), budget),
                f"barrier '{tag}' ({self.world} ranks)",
                timeout_ms=budget,
            )
            return
        base = self._key("barrier", tag)
        deadline = time.monotonic() + budget / 1000.0
        self._set(f"{base}/{self.rank}", "1", f"barrier '{tag}' arrival")
        for r in range(self.world):
            remaining_ms = max(1, int((deadline - time.monotonic()) * 1000))
            self._get(f"{base}/{r}", remaining_ms, f"barrier '{tag}' arrival of rank {r}")

    def all_gather_meta(
        self, name: str, meta: Dict[str, Any], timeout_ms: Optional[int] = None
    ) -> Dict[int, Dict[str, Any]]:
        """Every rank's ``meta`` dict, keyed by rank. JSON-sized payloads only."""
        budget = timeout_ms if timeout_ms is not None else self.timeout_ms
        base = self._key("gather", name, str(self._seq(f"gather/{name}")))
        deadline = time.monotonic() + budget / 1000.0
        self._set(f"{base}/{self.rank}", json.dumps(meta), f"all_gather '{name}' publish")
        out: Dict[int, Dict[str, Any]] = {}
        for r in range(self.world):
            remaining_ms = max(1, int((deadline - time.monotonic()) * 1000))
            out[r] = json.loads(self._get(f"{base}/{r}", remaining_ms, f"all_gather '{name}' of rank {r}"))
        return out

    # -- session epochs ------------------------------------------------------------ #

    def _epoch_key(self, role: str) -> str:
        return self._key("epoch", role)

    def begin_session(self, role: str = "writer") -> int:
        """Bump and adopt the fenced epoch for ``role``. Call ONCE per process
        incarnation, from the (re)starting owner of the role — a zombie of the
        previous incarnation keeps the old epoch and gets fenced out."""
        cur = self.kv.try_get(self._epoch_key(role))
        new = int(cur or 0) + 1
        self._set(self._epoch_key(role), str(new), f"epoch bump of role '{role}'")
        self._epoch = new
        self._seen_epoch = max(self._seen_epoch, new)
        self._fence_role = role
        return new

    def adopt_epoch(self, role: str = "writer") -> int:
        """Read the current epoch without bumping (readers, observers). A
        reader that adopted a role also re-reads its authoritative epoch on
        every chunk receipt — max-SEEN alone cannot fence a zombie that writes
        before any new-epoch envelope has arrived."""
        cur = self.kv.try_get(self._epoch_key(role))
        self._seen_epoch = max(self._seen_epoch, int(cur or 0))
        self._fence_role = role
        return self._seen_epoch

    @property
    def epoch(self) -> int:
        return self._epoch

    # -- heartbeat / liveness ------------------------------------------------------ #

    def heartbeat(self, payload: Optional[Dict[str, Any]] = None) -> None:
        self._hb_seq += 1
        beat = {"seq": self._hb_seq, "epoch": self._epoch, "t": time.time()}
        if payload:
            beat.update(payload)
        self._set(self._key("hb", str(self.rank)), json.dumps(beat), f"heartbeat of rank {self.rank}")
        self.counters["Resilience/heartbeats_sent"] += 1

    def peer_liveness(self, max_age_s: float = 30.0) -> Dict[int, Dict[str, Any]]:
        """Best-effort view of every rank's last heartbeat. Ages are computed
        from the SENDER's wall clock — coarse liveness, not clock-synced
        truth; the HealthSentinel treats a stale peer as a symptom, not a
        verdict."""
        out: Dict[int, Dict[str, Any]] = {}
        for r in range(self.world):
            raw = self.kv.try_get(self._key("hb", str(r)))
            if raw is None:
                out[r] = {"alive": False, "age_s": None, "epoch": None, "seq": 0}
                continue
            try:
                beat = json.loads(raw)
            except ValueError:
                out[r] = {"alive": False, "age_s": None, "epoch": None, "seq": 0}
                continue
            age = max(0.0, time.time() - float(beat.get("t", 0.0)))
            alive = age <= max_age_s
            if not alive:
                self.counters["Resilience/peer_stale_heartbeats"] += 1
            out[r] = {"alive": alive, "age_s": age, "epoch": beat.get("epoch"), "seq": beat.get("seq", 0)}
        return out

    # -- epoch-fenced chunk transport ---------------------------------------------- #
    #
    # Wire format: "<epoch>:<seq>:<crc32>:<b64 data>". The header is a few
    # bytes at the FRONT; CRC covers the payload, so a torn/corrupted value is
    # detected whether the damage hits the header (parse fails) or the body
    # (CRC mismatch). Acks ride a per-seq status key whose value CHANGES on
    # every reader verdict ("ok:<epoch>" / "bad:<n>" / "stale:<epoch>"); the
    # writer resends until it observes an "ok", a fencing "stale", or its
    # deadline. The reader advances a durable cursor after each delivery, so a
    # restarted writer resumes at cursor+1: at-most-once delivery per seq with
    # no gap.

    def _chunk_keys(self, channel: str, seq: int) -> Tuple[str, str]:
        return self._key("chan", channel, str(seq)), self._key("chan", channel, str(seq), "st")

    def chunk_cursor(self, channel: str) -> int:
        """Highest seq the reader has durably delivered (-1 before the first)."""
        raw = self.kv.try_get(self._key("chan", channel, "cursor"), timeout_ms=200)
        return int(raw) if raw is not None else -1

    def send_chunk(
        self,
        channel: str,
        seq: int,
        data: bytes,
        timeout_ms: Optional[int] = None,
        ack_poll_ms: int = 300,
    ) -> None:
        budget = timeout_ms if timeout_ms is not None else self.timeout_ms
        deadline = time.monotonic() + budget / 1000.0
        data_key, st_key = self._chunk_keys(channel, seq)
        payload = f"{self._epoch}:{seq}:{zlib.crc32(data) & 0xFFFFFFFF}:" + base64.b64encode(data).decode()
        last_st = self.kv.try_get(st_key, timeout_ms=50)
        first = True
        while True:
            if not first:
                self.counters["Resilience/chunk_resends"] += 1
            first = False
            fp = failpoints.failpoint("control.chunk_send", channel=channel, seq=seq, value=payload)
            wire = fp if isinstance(fp, str) else payload
            if fp is not failpoints.DROPPED:
                self._retry(
                    lambda w=wire: self.kv.set(data_key, w),
                    f"chunk send '{channel}'#{seq}",
                    timeout_ms=max(1, int((deadline - time.monotonic()) * 1000)),
                )
            ack_end = min(deadline, time.monotonic() + ack_poll_ms / 1000.0)
            while time.monotonic() < ack_end:
                st = self.kv.try_get(st_key, timeout_ms=50)
                if st is not None and st != last_st:
                    last_st = st
                    kind, _, rest = st.partition(":")
                    if kind == "ok":
                        return
                    if kind == "stale":
                        try:
                            fenced = int(rest) >= self._epoch
                        except ValueError:
                            fenced = True
                        if fenced:
                            raise StaleEpochError(
                                f"chunk send '{channel}'#{seq}: this writer's epoch "
                                f"{self._epoch} has been superseded — a newer incarnation "
                                "owns the channel; stop writing and exit"
                            )
                        # someone ELSE's zombie write was rejected on this key;
                        # it may have clobbered ours — fall through to resend
                    break  # "bad" (or foreign stale): resend now
                time.sleep(0.005)
            if time.monotonic() >= deadline:
                raise ControlPlaneTimeoutError(
                    f"chunk send '{channel}'#{seq} got no ack within {budget} ms "
                    f"(rank {self.rank}): the reader is likely dead or wedged"
                )

    def recv_chunk(self, channel: str, seq: int, timeout_ms: Optional[int] = None) -> bytes:
        budget = timeout_ms if timeout_ms is not None else self.timeout_ms
        deadline = time.monotonic() + budget / 1000.0
        data_key, st_key = self._chunk_keys(channel, seq)
        last_raw: Optional[str] = None
        bad = 0
        while time.monotonic() < deadline:
            try:
                raw = self.kv.get(data_key, timeout_ms=200)
            except Exception:
                continue
            fp = failpoints.failpoint("control.chunk_recv", channel=channel, seq=seq, value=raw)
            if isinstance(fp, str):
                raw = fp
            if raw == last_raw:
                time.sleep(0.005)
                continue
            last_raw = raw
            parsed = self._parse_chunk(raw, seq)
            if parsed is None:
                bad += 1
                self._set(st_key, f"bad:{bad}", f"chunk nack '{channel}'#{seq}")
                continue
            epoch, data = parsed
            if self._fence_role is not None and epoch >= self._seen_epoch:
                # the envelope claims to be current: verify against the
                # AUTHORITATIVE epoch key before accepting, so a zombie whose
                # write races ahead of its successor's first envelope still
                # gets fenced (one extra control-sized read per delivery)
                self.adopt_epoch(self._fence_role)
            if epoch < self._seen_epoch:
                self.counters["Resilience/stale_epoch_rejects"] += 1
                self._set(st_key, f"stale:{epoch}", f"chunk stale-reject '{channel}'#{seq}")
                continue
            self._seen_epoch = epoch
            self._set(st_key, f"ok:{epoch}", f"chunk ack '{channel}'#{seq}")
            self._set(self._key("chan", channel, "cursor"), str(seq), f"chunk cursor '{channel}'")
            return data
        raise ControlPlaneTimeoutError(
            f"chunk recv '{channel}'#{seq} saw no valid payload within {budget} ms "
            f"(rank {self.rank}): the writer is likely dead, or every attempt arrived torn"
        )

    @staticmethod
    def _parse_chunk(raw: str, want_seq: int) -> Optional[Tuple[int, bytes]]:
        try:
            epoch_s, seq_s, crc_s, b64 = raw.split(":", 3)
            epoch, seq, crc = int(epoch_s), int(seq_s), int(crc_s)
            data = base64.b64decode(b64, validate=True)
        except (ValueError, binascii.Error):
            return None
        if seq != want_seq or (zlib.crc32(data) & 0xFFFFFFFF) != crc:
            return None
        return epoch, data


# --------------------------------------------------------------------------- #
# module-level conveniences for the jax world (logger broadcast, Runtime barrier)
# --------------------------------------------------------------------------- #


def _world_plane(scope: str, timeout_ms: int, counters: Optional[Dict[str, int]] = None) -> ControlPlane:
    import jax

    client = require_coordinator_client("host control plane", counters)
    return ControlPlane(
        CoordinatorKV(client),
        rank=jax.process_index(),
        world=jax.process_count(),
        scope=scope,
        timeout_ms=timeout_ms,
    )


def host_broadcast_str(
    value: Optional[str], name: str = "bcast", timeout_ms: int = 600_000
) -> Optional[str]:
    """Process 0's ``value`` on every process, over the coordinator KV store;
    ``None`` when no coordinator client exists (caller picks its fallback).
    Repeated calls under one ``name`` stay matched through a process-global
    sequence — every process must make the same sequence of calls."""
    if coordinator_client() is None:
        return None
    plane = _world_plane("world", timeout_ms)
    key = plane._key("hostbcast", name, str(_next_seq(f"hostbcast/{name}")))
    if plane.rank == 0:
        plane._set(key, value if value is not None else "", f"host broadcast of '{name}'")
        return value
    return plane._get(key, timeout_ms, f"host broadcast of '{name}' from process 0")


def host_barrier(name: str = "sheeprl_tpu_barrier", timeout_ms: int = 600_000) -> bool:
    """All-process rendezvous over the coordinator's native barrier. Returns
    False when no coordinator client exists (caller picks its fallback)."""
    if coordinator_client() is None:
        return False
    plane = _world_plane("world", timeout_ms)
    native = getattr(plane.kv, "wait_at_barrier", None)
    native(f"{name}/{_next_seq(f'hostbarrier/{name}')}", timeout_ms)
    return True
