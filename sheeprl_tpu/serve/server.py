"""TCP frontend: JSON-lines protocol, graded degradation, graceful drain.

Protocol (one JSON object per line, either direction; responses carry the
request ``id`` and may arrive out of order on a pipelined connection):

- ``{"id": ..., "obs": {...}, "deadline_ms": 50, "priority": 1}`` ->
  ``{"id": ..., "status": "ok", "action": [...], "gen": 2}`` or a terminal
  backpressure answer: ``status`` in ``rejected`` (with ``retry_after_ms`` or
  ``reason: draining``), ``shed`` (with ``retry_after_ms``),
  ``deadline_expired``, ``error``. ``priority`` (optional, default 1; 0 =
  best-effort) selects the shed class under ``admission: shed_oldest`` —
  priority-0 traffic is shed before priority-1.
- ``{"op": "stats"}`` -> the ``Serve/*`` snapshot (plus compile totals).
- ``{"op": "health"}`` -> ``{"ready", "live", "degraded", "draining", "gen"}``.
- ``{"op": "metrics"}`` -> the whole metrics fabric as a Prometheus
  text-exposition body (``{"status": "ok", "text": ...}``) — scrape it off
  the same socket, no second listener.
- ``{"op": "profile", "action": "start|stop|toggle"}`` -> toggle an
  on-demand ``jax.profiler`` capture window on the live server
  (:mod:`sheeprl_tpu.telemetry.device`).

Shutdown contract (the chaos drill's core assertion): on SIGTERM the server
stops ADMITTING (new requests get ``rejected/draining`` — still a response),
drains everything already admitted, writes a final stats file, and only then
exits. Every request that ever reached the server gets exactly one answer.
"""

from __future__ import annotations

import json
import logging
import os
import socketserver
import threading
from typing import Any, Callable, Dict, Optional

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.core.resilience import PreemptionGuard
from sheeprl_tpu.serve import resolve
from sheeprl_tpu.serve.batcher import MicroBatcher
from sheeprl_tpu.serve.engine import GenerationStore, PolicyEngine
from sheeprl_tpu.serve.reload import HotReloader
from sheeprl_tpu.serve.stats import ServeStats
from sheeprl_tpu.telemetry import device as tel_device
from sheeprl_tpu.telemetry import export as tel_export
from sheeprl_tpu.telemetry import registry as tel_registry
from sheeprl_tpu.telemetry import trace

_logger = logging.getLogger(__name__)


class _Handler(socketserver.StreamRequestHandler):
    def handle(self) -> None:
        server: "PolicyServer" = self.server.policy_server  # type: ignore[attr-defined]
        wlock = threading.Lock()

        def send(obj: Dict[str, Any]) -> None:
            data = (json.dumps(obj) + "\n").encode()
            with wlock:
                try:
                    self.wfile.write(data)
                    self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError, OSError):
                    pass  # client went away; its request still resolved in the stats

        while True:
            try:
                line = self.rfile.readline()
            except (ConnectionResetError, OSError):
                return
            if not line:
                return
            line = line.strip()
            if not line:
                continue
            try:
                msg = json.loads(line)
            except ValueError:
                send({"status": "error", "error": "malformed json"})
                continue
            op = msg.get("op", "infer")
            if op == "stats":
                send(server.stats_payload())
            elif op == "health":
                send(server.health_payload())
            elif op == "metrics":
                send(server.metrics_payload())
            elif op == "profile":
                send(server.profile_payload(msg))
            elif op == "infer":
                server.handle_infer(msg, send)
            else:
                send({"status": "error", "error": f"unknown op '{op}'"})


class _TCPServer(socketserver.ThreadingTCPServer):
    daemon_threads = True
    allow_reuse_address = True


class PolicyServer:
    def __init__(
        self,
        cfg: Any,
        state: Dict[str, Any],
        *,
        source: str = "boot",
        ckpt_dir: Optional[str] = None,
        boot_info: Optional[Dict[str, Any]] = None,
    ):
        self.sv = resolve(cfg)
        self.stats = ServeStats(latency_window=int(self.sv.server.latency_window))
        self.engine = PolicyEngine(cfg, state, source=source, boot_info=boot_info)
        self.store = GenerationStore(self.engine.boot_generation)
        self.stats.set_gauge("generation", self.store.gen_id)
        deadline_ms = float(self.sv.queue.deadline_ms)
        self.batcher = MicroBatcher(
            self._compute,
            max_batch=self.engine.max_batch,
            max_wait_s=float(self.sv.batch.max_wait_ms) / 1000.0,
            max_depth=int(self.sv.queue.max_depth),
            admission=str(self.sv.queue.admission),
            retry_after_ms=float(self.sv.queue.retry_after_ms),
            default_deadline_s=(deadline_ms / 1000.0) if deadline_ms > 0 else None,
            stats=self.stats,
        )
        self.reloader: Optional[HotReloader] = None
        if bool(self.sv.reload.enabled) and ckpt_dir and os.path.isdir(ckpt_dir):
            self.reloader = HotReloader(
                self.engine,
                self.store,
                ckpt_dir,
                self.stats,
                poll_s=float(self.sv.reload.poll_s),
                canary=bool(self.sv.reload.canary),
                degraded_after=int(self.sv.reload.degraded_after),
            )
        self._tcp: Optional[_TCPServer] = None
        self._tcp_thread: Optional[threading.Thread] = None
        self.host = str(self.sv.server.host)
        self.port = int(self.sv.server.port)
        # telemetry artifacts land beside the run's other outputs (the ckpt
        # dir's parent is the run dir when serving a recorded run; cwd-local
        # dirs otherwise)
        run_dir = os.path.dirname(os.path.abspath(ckpt_dir)) if ckpt_dir else os.getcwd()
        self.telemetry_dir = os.path.join(run_dir, "telemetry")
        self.profile_dir = os.path.join(self.telemetry_dir, "profiler")
        # plug this server's counters into the process-wide metrics fabric:
        # the `metrics` op (and any JsonlSink) sees Serve/Compile/Telemetry/
        # Device series in one snapshot
        tel_registry.register_default_providers()
        tel_registry.register("serve", self.stats.snapshot)

    # ----- lifecycle ------------------------------------------------------------------
    def start(self) -> "PolicyServer":
        """Warm every bucket, then open the listener. Ordering matters: the
        first request after 'ready' must dispatch AOT, not trace."""
        self.engine.warm_boot()
        self.batcher.start()
        if self.reloader is not None:
            self.reloader.start()
        self._tcp = _TCPServer((self.host, self.port), _Handler)
        self._tcp.policy_server = self  # type: ignore[attr-defined]
        self.port = self._tcp.server_address[1]
        self._tcp_thread = threading.Thread(
            target=self._tcp.serve_forever, name="sheeprl-serve-tcp", daemon=True
        )
        self._tcp_thread.start()
        self.stats.set_gauge("ready", 1.0 if self.engine.ready() else 0.0)
        self._write_ready_file()
        _logger.info("[serve] listening on %s:%d (gen %d)", self.host, self.port, self.store.gen_id)
        return self

    def _write_ready_file(self) -> None:
        ready_file = self.sv.server.ready_file
        if not ready_file:
            return
        tmp = f"{ready_file}.tmp"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "host": self.host,
                    "port": self.port,
                    "pid": os.getpid(),
                    # where the policy step runs (fabric.player_on_host decides):
                    # a supervisor or bench can see a CPU replica for what it is
                    "policy_device": str(self.engine.runtime.player_device),
                    "policy_platform": self.engine.runtime.player_device.platform,
                },
                f,
            )
        os.replace(tmp, ready_file)

    def serve_until_stopped(self, stats_file: Optional[str] = None, drain_timeout_s: float = 30.0) -> None:
        """Main-thread loop: block until SIGTERM/SIGINT, then drain + exit.
        The guard's ``on_signal`` wakes the wait instantly — a mid-drill kill
        should not cost up to a poll tick of extra in-flight exposure."""
        wake = threading.Event()
        with PreemptionGuard(enabled=True, on_signal=lambda _s: wake.set()) as guard:
            while not guard.should_stop:
                wake.wait(0.5)
            _logger.info("[serve] %s: draining", guard.describe())
            self.shutdown(stats_file=stats_file, drain_timeout_s=drain_timeout_s)

    def shutdown(self, stats_file: Optional[str] = None, drain_timeout_s: float = 30.0) -> bool:
        self.stats.set_gauge("ready", 0.0)
        drained = self.batcher.drain(timeout=drain_timeout_s)
        if not drained:
            _logger.warning("[serve] drain timed out after %.1fs", drain_timeout_s)
        if self.reloader is not None:
            self.reloader.stop()
        if self._tcp is not None:
            self._tcp.shutdown()
            self._tcp.server_close()
        self.batcher.close()
        tel_device.stop_capture()  # never leak an open profiler window across exit
        trace_path = None
        if trace.enabled():
            try:
                trace_path = trace.export(os.path.join(self.telemetry_dir, "trace.json"))
            except OSError:
                _logger.exception("[serve] trace export failed")
        if stats_file:
            payload = self.stats_payload()
            payload["drained"] = drained
            if trace_path:
                payload["trace_path"] = trace_path
                payload["trace_id"] = trace.current_trace_id()
            tmp = f"{stats_file}.tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=2)
            os.replace(tmp, stats_file)
        return drained

    # ----- request path ---------------------------------------------------------------
    def _compute(self, requests) -> list:
        # ONE store read pins the whole batch to a single generation: a swap
        # landing mid-batch affects the NEXT batch, never this one (no torn
        # reads across a batch)
        gen = self.store.get()
        actions = self.engine.act(gen.params, [r.obs for r in requests])
        return [
            {"action": actions[i].tolist(), "gen": gen.gen_id, "step": gen.step}
            for i in range(len(requests))
        ]

    def handle_infer(self, msg: Dict[str, Any], send: Callable[[Dict[str, Any]], None]) -> None:
        rid = msg.get("id")
        try:
            obs = self.engine.coerce_obs(msg.get("obs"))
        except ValueError as e:
            self.stats.inc("requests_total")
            self.stats.inc("errors")
            send({"id": rid, "status": "error", "error": str(e)})
            return
        deadline_ms = msg.get("deadline_ms")
        deadline_s = None if deadline_ms is None else float(deadline_ms) / 1000.0
        try:
            priority = max(0, int(msg.get("priority", 1)))
        except (TypeError, ValueError):
            priority = 1  # a malformed class must not cost the request
        fut = self.batcher.submit(obs, deadline_s=deadline_s, rid=rid, priority=priority)
        fut.add_done_callback(lambda f: send(f.result()))

    # ----- observability --------------------------------------------------------------
    def stats_payload(self) -> Dict[str, Any]:
        payload = self.stats.snapshot()
        compile_totals = jax_compile.process_stats()
        payload["Compile/retraces"] = compile_totals["retraces"]
        payload["Compile/aot_compiles"] = compile_totals["aot_compiles"]
        try:
            fp = self.engine.program_footprint()
            payload["Programs/act_executables"] = fp["programs"]
            payload["Programs/act_peak_hbm_bytes_max"] = fp["peak_hbm_bytes_max"]
            payload["Programs/act_compile_seconds_total"] = fp["compile_seconds_total"]
        except Exception:  # the ledger is observability; stats must stay up
            pass
        return payload

    def metrics_payload(self) -> Dict[str, Any]:
        """The whole metrics fabric as Prometheus text (the ``metrics`` op)."""
        try:
            text = tel_export.to_prometheus()
        except Exception as e:  # the fabric must not crash the frontend
            return {"status": "error", "error": f"{type(e).__name__}: {e}"}
        return {
            "status": "ok",
            "content_type": "text/plain; version=0.0.4",
            "trace_id": trace.current_trace_id(),
            "text": text,
        }

    def profile_payload(self, msg: Dict[str, Any]) -> Dict[str, Any]:
        """On-demand jax.profiler window (the ``profile`` op): ``action`` in
        start | stop | toggle; ``dir`` overrides the capture directory."""
        action = str(msg.get("action", "toggle"))
        cap_dir = str(msg.get("dir") or self.profile_dir)
        try:
            if action == "start":
                state = "started" if tel_device.start_capture(cap_dir) else "busy"
            elif action == "stop":
                state = "stopped" if tel_device.stop_capture() else "idle"
            elif action == "toggle":
                state = tel_device.toggle_capture(cap_dir)
            else:
                return {"status": "error", "error": f"unknown profile action '{action}'"}
        except Exception as e:
            return {"status": "error", "error": f"{type(e).__name__}: {e}"}
        return {"status": "ok", "profile": state, "dir": cap_dir}

    def health_payload(self) -> Dict[str, Any]:
        snap = self.stats.snapshot()
        live = self.batcher._thread is not None and self.batcher._thread.is_alive()
        return {
            "ready": bool(snap["Serve/ready"]) and live,
            "live": live,
            "degraded": bool(snap["Serve/degraded"]),
            "draining": bool(snap["Serve/draining"]),
            "gen": self.store.gen_id,
        }
