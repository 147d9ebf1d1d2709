"""In-process structured tracer: ring-buffered spans, Perfetto-compatible export.

One process-wide :class:`Tracer` (or None) correlates events across the three
planes (train / serve / orchestrate) under a single **trace id** — a run-scoped
hex token stamped into every span, every ``health/events.jsonl`` row, every
failpoint hit record, and every certified-checkpoint sidecar, so a rollback or
canary failure is attributable to the exact iteration/request that tripped it.

Like :mod:`sheeprl_tpu.core.failpoints`, the instrumentation seams are
**zero-cost no-ops unless activated**: the fast path of :func:`span` /
:func:`instant` / :func:`add_span` is a single module-global ``is None``
identity check returning a shared singleton — no allocation, no string work,
no lock (guarded by ``tests/test_utils/test_telemetry.py``). Production
binaries pay nothing for carrying spans in their hot loops.

Activation comes from the ``SHEEPRL_TPU_TRACE`` environment variable (read
once at import, so subprocess drills and serve children inherit the trace —
and, via an embedded ``trace_id``, join the PARENT's trace) or
programmatically via :func:`configure`::

    SHEEPRL_TPU_TRACE=1
    SHEEPRL_TPU_TRACE="plane=serve;capacity=8192;trace_id=ab12cd34ef56"

Spans also **follow a profiler capture**. The train plane hands this module
``jax.profiler.TraceAnnotation`` once (:func:`follow_captures`, from
``cli._apply_global_flags``; this module never imports jax, because the router
and the controller never do). From then on, while a ``jax.profiler`` session
is open (``metric.profiler``, SIGUSR2, ``CaptureWindow``), every span is
recorded whether or not a tracer is configured, and every :func:`span` also
enters ``TraceAnnotation("sheeprl.<name>")`` on the thread that made it, so it
lies in the capture's ``.xplane.pb`` on the device events' clock. With no
tracer configured the spans of a capture land in a ring of their own, made
anew when a capture opens: after the capture, ``get_tracer().events()`` holds
exactly its spans. With no session open that path costs one more call
(``TraceAnnotation.is_enabled()``, about 0.1 us) and still allocates nothing.

Completed spans land in a bounded ring (``collections.deque(maxlen=...)``):
steady-state memory is O(capacity), the newest events win, and
``Telemetry/spans_dropped`` counts what the ring evicted. :func:`export`
writes the ring as Chrome trace-event JSON (``{"traceEvents": [...]}``,
"ph":"X" complete events with microsecond ``ts``/``dur``) that loads directly
in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.
"""

from __future__ import annotations

import json
import os
import threading
import time
import uuid
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

ENV_VAR = "SHEEPRL_TPU_TRACE"

# Event tuple layout inside the ring (kept flat and allocation-light; dicts are
# built once, at export): (name, plane, ph, ts_us, dur_us, tid, span_id,
# parent_id, args-or-None).
_EV_NAME, _EV_PLANE, _EV_PH, _EV_TS, _EV_DUR, _EV_TID, _EV_SID, _EV_PARENT, _EV_ARGS = range(9)


class _NoopSpan:
    """Shared do-nothing span handle returned while tracing is disabled.

    A singleton: the disabled fast path must not allocate (mirrors the
    failpoints guarantee), so every disabled ``span()`` call returns THIS
    object. It supports the full live-span surface as no-ops."""

    __slots__ = ()
    span_id = ""
    trace_id = ""

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set(self, **args: Any) -> "_NoopSpan":
        return self


_NOOP = _NoopSpan()

# None <=> disabled: span()/instant()/add_span() must do NOTHING beyond this
# identity check (the entire production cost of carrying instrumentation).
_tracer: Optional["Tracer"] = None
_tls = threading.local()

ANNOTATION_PREFIX = "sheeprl."
# ``jax.profiler.TraceAnnotation`` once the train plane handed it over
# (follow_captures), else None: ``.is_enabled()`` says whether a profiler
# session is open, and an instance is the span inside the capture.
_annotation: Any = None
# The ring of the spans of a capture taken with no tracer configured: made
# when the first span of an open session arrives, kept after the session so
# that it can be read, replaced by the next capture's.
_capture: Optional["Tracer"] = None
_capture_open = False
_capture_lock = threading.Lock()


class Span:
    """A live span: context manager recording [enter, exit) into the ring."""

    __slots__ = ("name", "plane", "span_id", "parent_id", "args", "_t0", "_tracer", "_tid", "_ann")

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        plane: Optional[str],
        args: Dict[str, Any],
        parent_id: Optional[str] = None,
        annotation: Any = None,
    ):
        self._tracer = tracer
        self.name = name
        self.plane = plane or tracer.plane
        self.span_id = tracer._next_span_id()
        # None: the enclosing span of this thread (found at entry); a string: the
        # parent a caller on another thread handed over
        self.parent_id = parent_id
        self.args = args or None
        self._t0 = 0.0
        self._tid = 0
        self._ann = annotation

    @property
    def trace_id(self) -> str:
        return self._tracer.trace_id

    def set(self, **args: Any) -> "Span":
        """Attach/overwrite span args after entry (e.g. a result count)."""
        if self.args is None:
            self.args = dict(args)
        else:
            self.args.update(args)
        return self

    def __enter__(self) -> "Span":
        stack = _span_stack()
        if self.parent_id is None:
            self.parent_id = stack[-1] if stack else ""
        stack.append(self.span_id)
        self._tid = threading.get_ident()
        if self._ann is not None:  # outside the timed interval: the ring's span lies inside the capture's
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = _span_stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        if exc_type is not None:
            self.set(error=f"{exc_type.__name__}: {exc}")
        t = self._tracer
        t._record(
            (
                self.name,
                self.plane,
                "X",
                t._perf_to_us(self._t0),
                (t1 - self._t0) * 1e6,
                self._tid,
                self.span_id,
                self.parent_id,
                self.args,
            )
        )
        return False


def _span_stack() -> List[str]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


class Tracer:
    """Ring-buffered trace recorder; one per process, installed via
    :func:`configure`. Not used directly from instrumentation sites — those go
    through the module-level :func:`span`/:func:`instant`/:func:`add_span`."""

    def __init__(
        self,
        *,
        plane: str = "train",
        capacity: int = 16384,
        trace_id: Optional[str] = None,
        export_path: Optional[str] = None,
    ):
        self.plane = str(plane)
        self.capacity = max(int(capacity), 1)
        self.trace_id = (trace_id or uuid.uuid4().hex[:16]).strip()
        self.export_path = export_path
        self._ring: Deque[Tuple] = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._span_seq = 0
        self.spans_recorded = 0
        self.spans_dropped = 0
        # Clock anchors: spans time with perf_counter (monotonic, highest
        # resolution); serve request timestamps arrive on time.monotonic; the
        # export wants wall-anchored microseconds. One simultaneous sample of
        # all three pins the conversions for the process lifetime.
        wall, mono, perf = time.time(), time.monotonic(), time.perf_counter()
        self._epoch_minus_perf = wall - perf
        self._epoch_minus_mono = wall - mono

    # ----- time bases -----------------------------------------------------------
    def _perf_to_us(self, perf_s: float) -> float:
        return (perf_s + self._epoch_minus_perf) * 1e6

    def _mono_to_us(self, mono_s: float) -> float:
        return (mono_s + self._epoch_minus_mono) * 1e6

    # ----- recording ------------------------------------------------------------
    def _next_span_id(self) -> str:
        with self._lock:
            self._span_seq += 1
            return f"{self.trace_id}-{self._span_seq:x}"

    def _record(self, ev: Tuple) -> None:
        with self._lock:
            if len(self._ring) == self.capacity:
                self.spans_dropped += 1
            self._ring.append(ev)
            self.spans_recorded += 1

    # ----- read side ------------------------------------------------------------
    def events(self) -> List[Tuple]:
        with self._lock:
            return list(self._ring)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "Telemetry/enabled": 1,
                "Telemetry/spans_recorded": self.spans_recorded,
                "Telemetry/spans_dropped": self.spans_dropped,
                "Telemetry/ring_size": len(self._ring),
                "Telemetry/ring_capacity": self.capacity,
            }

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The ring as a Chrome trace-event / Perfetto-compatible object."""
        pid = os.getpid()
        trace_events: List[Dict[str, Any]] = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": f"sheeprl-{self.plane}"},
            }
        ]
        for ev in self.events():
            args = dict(ev[_EV_ARGS]) if ev[_EV_ARGS] else {}
            args["trace_id"] = self.trace_id
            if ev[_EV_SID]:
                args["span_id"] = ev[_EV_SID]
            if ev[_EV_PARENT]:
                args["parent_id"] = ev[_EV_PARENT]
            out = {
                "name": ev[_EV_NAME],
                "cat": ev[_EV_PLANE],
                "ph": ev[_EV_PH],
                "ts": ev[_EV_TS],
                "pid": pid,
                "tid": ev[_EV_TID],
                "args": args,
            }
            if ev[_EV_PH] == "X":
                out["dur"] = ev[_EV_DUR]
            elif ev[_EV_PH] == "i":
                out["s"] = "t"  # instant scoped to its thread
            trace_events.append(out)
        return {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "metadata": {
                "trace_id": self.trace_id,
                "plane": self.plane,
                "pid": pid,
                "spans_recorded": self.spans_recorded,
                "spans_dropped": self.spans_dropped,
            },
        }

    def export(self, path: Optional[str] = None) -> str:
        """Write the Chrome-trace JSON (atomic rename) and return its path."""
        path = path or self.export_path or f"trace_{self.trace_id}.json"
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        return path


# --------------------------------------------------------------------------- #
# instrumentation surface (the only API call sites use)
# --------------------------------------------------------------------------- #


def _recorder() -> Optional["Tracer"]:
    """The tracer that records now: the configured one, else the capture's ring
    while a profiler session is open, else None."""
    t = _tracer
    if t is not None:
        return t
    a = _annotation
    if a is None:
        return None
    if not a.is_enabled():
        if _capture_open:
            _close_capture()
        return None
    return _capture_ring()


def span(name: str, plane: Optional[str] = None, *, parent_id: Optional[str] = None, **args: Any) -> Any:
    """A context-manager span. Returns the shared no-op singleton when tracing
    is disabled and no profiler session is open — the fast path is one identity
    check (and, once :func:`follow_captures` ran, one ``is_enabled()``), zero
    allocation. ``plane`` overrides the tracer's default category (e.g. a
    serve-side span recorded from a process whose tracer was configured for
    train). ``parent_id`` names the parent when it lives on another thread
    (:func:`current_span_id` there, handed over with the work); left out, the
    parent is the enclosing span of this thread."""
    t = _recorder()
    if t is None:  # the entire production cost of an instrumentation seam
        return _NOOP
    return _begin(t, name, plane, args, parent_id)


def instant(name: str, **args: Any) -> None:
    """A zero-duration marker event (e.g. a failpoint fire, a trial state
    transition). No-op while disabled."""
    t = _recorder()
    if t is None:
        return None
    return _record_instant(t, name, args)


def add_span(
    name: str,
    start_s: float,
    end_s: float,
    *,
    clock: str = "monotonic",
    plane: Optional[str] = None,
    parent_id: str = "",
    span_id: str = "",
    **args: Any,
) -> None:
    """Record a completed span from explicit timestamps (``time.monotonic`` or
    ``time.perf_counter`` values, per ``clock``) — the cross-thread form used
    by the serve request lifecycle, where admit and respond happen on
    different threads than the batch compute. A caller that pre-allocated an
    id with :func:`new_span_id` (to hand children a parent before the parent
    closes) passes it as ``span_id``. No-op while disabled. A span added after
    the fact reaches the ring only: a capture holds what was annotated while it
    ran (:func:`span`)."""
    t = _recorder()
    if t is None:
        return None
    return _record_span(t, name, start_s, end_s, clock, plane, parent_id, span_id, args)


def new_span_id() -> str:
    """Pre-allocate a span id for a later :func:`add_span` (lets cross-thread
    children link to a parent that has not closed yet); ``""`` while
    disabled."""
    t = _recorder()
    return t._next_span_id() if t is not None else ""


# Kept module-level (not methods) so the disabled-mode zero-cost test can
# monkeypatch them to raise and prove span()/instant()/add_span() never reach
# past the `_tracer is None` guard — the same pattern as failpoints._fire.
def _begin(t: Tracer, name: str, plane: Optional[str], args: Dict[str, Any], parent_id: Optional[str] = None) -> Span:
    a = _annotation
    ann = a(ANNOTATION_PREFIX + name) if a is not None and a.is_enabled() else None
    return Span(t, name, plane, args, parent_id, ann)


def _capture_ring() -> Tracer:
    """The ring of the open capture, made when its first span arrives."""
    global _capture, _capture_open
    with _capture_lock:
        if not _capture_open:
            _capture = Tracer(plane="train")
            _capture_open = True
        return _capture


def _close_capture() -> None:
    """The session ended: the ring stays to be read, the next capture starts a new one."""
    global _capture_open
    with _capture_lock:
        _capture_open = False


def _record_instant(t: Tracer, name: str, args: Dict[str, Any]) -> None:
    stack = _span_stack()
    t._record(
        (
            name,
            t.plane,
            "i",
            t._perf_to_us(time.perf_counter()),
            0.0,
            threading.get_ident(),
            "",
            stack[-1] if stack else "",
            args or None,
        )
    )


def _record_span(
    t: Tracer,
    name: str,
    start_s: float,
    end_s: float,
    clock: str,
    plane: Optional[str],
    parent_id: str,
    span_id: str,
    args: Dict[str, Any],
) -> None:
    conv = t._mono_to_us if clock == "monotonic" else t._perf_to_us
    t._record(
        (
            name,
            plane or t.plane,
            "X",
            conv(start_s),
            max(end_s - start_s, 0.0) * 1e6,
            threading.get_ident(),
            span_id or t._next_span_id(),
            parent_id,
            args or None,
        )
    )


# --------------------------------------------------------------------------- #
# lifecycle / introspection
# --------------------------------------------------------------------------- #


def configure(
    enabled: bool = True,
    *,
    plane: str = "train",
    capacity: int = 16384,
    trace_id: Optional[str] = None,
    export_path: Optional[str] = None,
) -> Optional[Tracer]:
    """(Re)install the process tracer; ``enabled=False`` disables tracing.

    Also mirrors the active settings into ``os.environ[SHEEPRL_TPU_TRACE]`` so
    subprocesses spawned after this point (orchestrator trials, serve
    children, bench workers) inherit tracing AND the same trace id — one trace
    id across the whole process tree is what makes cross-plane correlation
    work."""
    global _tracer
    if not enabled:
        _tracer = None
        os.environ.pop(ENV_VAR, None)
        return None
    t = Tracer(plane=plane, capacity=capacity, trace_id=trace_id, export_path=export_path)
    _tracer = t
    os.environ[ENV_VAR] = f"plane={t.plane};capacity={t.capacity};trace_id={t.trace_id}"
    return t


def configure_from_env(environ: Optional[Dict[str, str]] = None) -> Optional[Tracer]:
    """Activate from ``SHEEPRL_TPU_TRACE`` (``1`` or ``k=v;k=v`` pairs:
    ``plane``, ``capacity``, ``trace_id``, ``export``)."""
    spec = (environ if environ is not None else os.environ).get(ENV_VAR)
    if not spec:
        return None
    kv: Dict[str, str] = {}
    for part in spec.split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            kv[k.strip()] = v.strip()
    if not kv and spec.strip().lower() not in ("1", "on", "true", "yes"):
        return None
    return configure(
        plane=kv.get("plane", "train"),
        capacity=int(kv.get("capacity", 16384)),
        trace_id=kv.get("trace_id") or None,
        export_path=kv.get("export") or None,
    )


def follow_captures(annotation: Any) -> None:
    """Hand over ``jax.profiler.TraceAnnotation`` (None takes it back): from now
    on spans are recorded and annotated while a profiler session is open (module
    docstring). Called by ``cli._apply_global_flags``, so by every train loop and
    by anything that sets a process up the way ``cli.run`` does."""
    global _annotation, _capture, _capture_open
    with _capture_lock:
        _annotation = annotation
        if annotation is None:
            _capture, _capture_open = None, False


def disable() -> None:
    configure(False)


def enabled() -> bool:
    return _tracer is not None


def get_tracer() -> Optional[Tracer]:
    """The configured tracer, else the ring of the newest capture (or None)."""
    return _tracer if _tracer is not None else _capture


def current_trace_id() -> str:
    """The process trace id, or ``""`` while disabled. Cheap enough for event
    rows and sidecars to call unconditionally."""
    t = _tracer
    return t.trace_id if t is not None else ""


def current_span_id() -> str:
    """The innermost open span of this thread, ``""`` where there is none."""
    if _tracer is None and not _capture_open:
        return ""
    stack = _span_stack()
    return stack[-1] if stack else ""


def stats() -> Dict[str, Any]:
    """``Telemetry/*`` counters for the metrics fabric (works while disabled)."""
    t = _tracer
    if t is None:
        return {"Telemetry/enabled": 0}
    return t.stats()


def export(path: Optional[str] = None) -> Optional[str]:
    """Export the active tracer's ring; None while disabled."""
    t = _tracer
    return t.export(path) if t is not None else None


# Subprocess drills set SHEEPRL_TPU_TRACE in the child env; reading it at
# import means every entry point (sheeprl.py, serve, orchestrate, bench
# children) joins the parent's trace with no plumbing.
configure_from_env()
