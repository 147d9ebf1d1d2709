"""Compilation management: AOT warmup, retrace guard, persistent-cache stats.

Every run pays XLA compile latency on the critical path unless something manages
it: the first train step blocks on tracing+compiling the fused ``lax.scan``
update, and any silent shape/dtype drift mid-run retraces it again — invisible
except as a throughput cliff. This module turns compilation into a managed,
observable resource (the Podracer recipe: compile once, ahead of time, never
retrace in steady state):

- :func:`guarded_jit` wraps ``jax.jit`` with a per-function trace counter, an
  abstract-signature log (every retrace logs the diff against the previous
  signature), a ``warn``/``halt`` policy once the loop declares steady state
  (:func:`mark_steady`), and a registry of AOT-compiled executables that
  matching calls route to WITHOUT touching the jit tracing machinery.
- :class:`AOTWarmup` compiles registered entry points from
  ``jax.ShapeDtypeStruct`` specs on a background thread, overlapped with env
  reset / first-rollout collection, so the accelerator is warm before step 0.
  ``jit(f).lower(specs).compile()`` alone does NOT populate the jit call cache
  (a later ``f(args)`` would re-trace), which is why the guard keeps the
  compiled executable and routes calls to it by abstract signature (read only
  at the leaves that tell its executables apart: :meth:`GuardedFn._reselect`).
- cache listeners count persistent-compilation-cache hits/misses and sum JAX's
  own compile durations (``jax.monitoring`` events: tracing and lowering, XLA
  compile, loads from the persistent cache), and :func:`drain_compile_counters`
  folds all counters into a ``MetricAggregator`` at log boundaries
  (``Compile/retraces``, ``Compile/cache_hits``, ``Compile/cache_misses``,
  ``Time/compile_seconds``).
- :class:`setup_phase` keeps the record of a process's set-up: ``(name,
  start, end)`` of each phase (the package's import, ``compose``, the
  runtime, ``build_agent``, ``make_train_fn``, the wait for the AOT warmup,
  every guarded compile), read through :func:`process_stats`.
- :func:`pow2_bucket` / :func:`bucketed_pad` are the shared canonical-shape
  utilities (generalized from ppo_recurrent's inline episode bucketing) so
  variable-length sequences / partial final batches land in a bounded set of
  padded shapes instead of a fresh compile each.

Config: the ``compile:`` Hydra group (``configs/compile/default.yaml``), read
through :func:`resolve` which fills defaults when the group is absent (configs
recorded before this subsystem existed keep working).
"""

from __future__ import annotations

import functools
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax

from sheeprl_tpu.telemetry import trace

_logger = logging.getLogger("sheeprl_tpu.compile")

# process-relative clock zero for ``first_call_s`` (time-to-first-step metrics)
_T0 = time.perf_counter()

# Wrapper callables whose function arguments enter a jax trace. This is the
# root set of sheeprl_tpu.analysis's jit-reachability call graph, which reads
# it STATICALLY (ast.literal_eval) — keep it a pure literal tuple of final
# name segments ("jax.jit" and "jit" both match "jit"). The builtin-colliding
# "map" (lax.map) is deliberately absent: matching every call to map() would
# drown the graph in false entry points.
JIT_ENTRY_WRAPPERS: Tuple[str, ...] = (
    "jit",
    "guarded_jit",
    "aot_compile",
    "shard_map",
    "_shard_map",
    "scan",
    "associative_scan",
    "fori_loop",
    "while_loop",
    "cond",
    "switch",
    "vmap",
    "pmap",
    "grad",
    "value_and_grad",
    "checkpoint",
    "remat",
    "custom_vjp",
    "custom_jvp",
)

# --------------------------------------------------------------------------- #
# Config group
# --------------------------------------------------------------------------- #

_DEFAULTS: Dict[str, Dict[str, Any]] = {
    "cache": {"min_compile_time_secs": None},
    "aot": {"enabled": True},
    "guard": {"policy": "warn"},
}

_POLICIES = ("warn", "halt", "off")


class _View:
    """Attribute access over the merged defaults (same shape as resilience._View)."""

    def __init__(self, merged: Dict[str, Dict[str, Any]]):
        for section, values in merged.items():
            setattr(self, section, _Section(values))


class _Section:
    def __init__(self, values: Dict[str, Any]):
        self.__dict__.update(values)

    def get(self, key, default=None):
        return self.__dict__.get(key, default)


def resolve(cfg: Any) -> _View:
    """Defaults-filled view of ``cfg.compile``; tolerates a missing group entirely
    (resumed sidecar configs predating this subsystem have no ``compile:``)."""
    try:
        group = cfg.get("compile") if hasattr(cfg, "get") else None
    except Exception:
        group = None
    merged: Dict[str, Dict[str, Any]] = {}
    for section, defaults in _DEFAULTS.items():
        got = None
        if group is not None:
            got = group.get(section) if hasattr(group, "get") else getattr(group, section, None)
        merged[section] = dict(defaults)
        if got is not None:
            for k in defaults:
                v = got.get(k, defaults[k]) if hasattr(got, "get") else getattr(got, k, defaults[k])
                merged[section][k] = v
    cache_group = group.get("cache") if group is not None and hasattr(group, "get") else None
    if cache_group is not None and cache_group.get("dir"):
        raise ValueError(
            "compile.cache.dir is gone: the persistent compile cache lives where "
            "JAX_COMPILATION_CACHE_DIR says, else at sheeprl_tpu.COMPILE_CACHE_DIR "
            f"(got compile.cache.dir={cache_group.get('dir')!r})"
        )
    policy = str(merged["guard"]["policy"]).lower()
    if policy not in _POLICIES:
        raise ValueError(f"compile.guard.policy must be one of {_POLICIES}; got {policy!r}")
    merged["guard"]["policy"] = policy
    return _View(merged)


def aot_enabled(cfg: Any) -> bool:
    """Whether the train loops should register + run AOT warmup for this run."""
    return bool(resolve(cfg).aot.enabled)


# --------------------------------------------------------------------------- #
# Process-wide state
# --------------------------------------------------------------------------- #

_LOCK = threading.Lock()
_REGISTRY: List["GuardedFn"] = []
_STEADY = False
_GUARD_POLICY = "warn"
_CACHE_COUNTS = {"cache_hits": 0, "cache_misses": 0}
# AOT warmup jobs that raised (process total): the run survives them by
# compiling on first call, which is exactly what a smoke must be able to see
_WARMUP_ERRORS = 0
_LISTENER_INSTALLED = False
# snapshot of process totals at the last drain_compile_counters() call
_DRAINED: Dict[str, float] = {}

_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
# JAX's own compile durations, summed into the keys process_stats() returns. They
# count every program of the PROCESS, whoever made it (a benchmark's reference, a
# test's helpers), not only the guarded functions.
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration", "/jax/core/compile/jaxpr_to_mlir_module_duration")
# one compile request: JAX times the whole of `compile_or_get_cached` under this
# event, a load from the persistent cache (`cache_retrieval_time_sec`) included
_REQUEST_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_TOTALS: Dict[str, float] = {
    "trace_seconds": 0.0, "trace_count": 0,
    "backend_compile_seconds": 0.0, "backend_compile_count": 0,
    "cache_retrieval_seconds": 0.0, "cache_retrieval_count": 0,
}
# per thread: how deep in JAX's open traces and lowerings it is, the seconds of
# the outermost ones it closed (GuardedFn's jit path reads the difference), and
# for each open compile request whether the persistent cache answered it
_jax_tls = threading.local()

# Aggregator keys this module feeds (register them in configs/metric/default.yaml
# and each algo's AGGREGATOR_KEYS or the CLI prunes them).
METRIC_KEYS = (
    "Compile/retraces",
    "Compile/cache_hits",
    "Compile/cache_misses",
    "Time/compile_seconds",
)


def install_cache_listeners() -> None:
    """Count persistent-cache hit/miss events and sum JAX's compile durations
    (idempotent; the listeners are global, installed when this module is imported).

    ``trace_seconds`` is jaxpr tracing plus lowering to MLIR; a trace opened inside
    another (a nested ``jit``) is part of the outer one and is not added again (JAX
    records each interval's start as a scalar event, which keeps the depth per
    thread). JAX's ``backend_compile_duration`` times a whole compile request, a
    load from the persistent cache included, so each request is booked once, by
    how it was served: ``cache_retrieval_seconds`` if the cache answered it (from
    the request to the loaded executable: the key, the read, the load), else
    ``backend_compile_seconds``, XLA's compile of what the cache did not hold. Each
    has a ``*_count``; the two sum to JAX's own total of the event."""
    global _LISTENER_INSTALLED
    with _LOCK:
        if _LISTENER_INSTALLED:
            return
        _LISTENER_INSTALLED = True

    def _listener(event: str, **kwargs) -> None:
        key = _CACHE_EVENTS.get(event)
        if key is not None:
            with _LOCK:
                _CACHE_COUNTS[key] += 1

    def _started(event: str, value: float, **kwargs) -> None:
        if event in _TRACE_EVENTS:
            _jax_tls.depth = getattr(_jax_tls, "depth", 0) + 1
        elif event == _REQUEST_EVENT:
            _open_requests().append(False)

    def _ended(event: str, duration: float, **kwargs) -> None:
        if event in _TRACE_EVENTS:
            depth = getattr(_jax_tls, "depth", 0)
            _jax_tls.depth = max(depth - 1, 0)
            if depth > 1:  # inside an open trace or lowering: its seconds are the outer one's
                return
            _jax_tls.trace_seconds = _thread_trace_seconds() + duration
            kind = "trace"
        elif event == _RETRIEVAL_EVENT:
            requests = _open_requests()
            if requests:  # the open request was served by the cache: booked whole at its end
                requests[-1] = True
                return
            kind = "cache_retrieval"
        elif event == _REQUEST_EVENT:
            requests = _open_requests()
            kind = "cache_retrieval" if requests and requests.pop() else "backend_compile"
        else:
            return
        with _LOCK:
            _JAX_TOTALS[f"{kind}_seconds"] += duration
            _JAX_TOTALS[f"{kind}_count"] += 1

    jax.monitoring.register_event_listener(_listener)
    jax.monitoring.register_scalar_listener(_started)
    jax.monitoring.register_event_duration_secs_listener(_ended)


def _open_requests() -> List[bool]:
    """This thread's open compile requests, innermost last: whether the cache answered each."""
    requests = getattr(_jax_tls, "requests", None)
    if requests is None:
        requests = _jax_tls.requests = []
    return requests


def _thread_trace_seconds() -> float:
    """Seconds of outermost traces and lowerings this thread has closed."""
    return getattr(_jax_tls, "trace_seconds", 0.0)


def configure(cfg: Any) -> _View:
    """Apply the ``compile:`` group for a new run.

    Sets the retrace policy, clears the steady-state watermark (a fresh run's
    first traces are not retraces of the previous run), applies the
    persist threshold to jax.config ONLY when explicitly set, and installs the
    cache-stats listeners. The cache DIRECTORY is never set here: it is
    ``JAX_COMPILATION_CACHE_DIR`` or the fixed path ``sheeprl_tpu/__init__.py``
    chose at import.
    """
    cc = resolve(cfg)
    global _GUARD_POLICY, _STEADY
    _GUARD_POLICY = cc.guard.policy
    _STEADY = False
    if cc.cache.min_compile_time_secs is not None:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", float(cc.cache.min_compile_time_secs)
        )
    install_cache_listeners()
    return cc


def mark_steady() -> None:
    """Steady-state watermark: the loops call this once their first full
    iteration (rollout + train) has compiled everything it is going to; any
    retrace after this point is a perf cliff and escalates per the policy."""
    global _STEADY
    _STEADY = True


def is_steady() -> bool:
    return _STEADY


# --------------------------------------------------------------------------- #
# Set-up phases
# --------------------------------------------------------------------------- #

# (name, start, end) on time.perf_counter, the clock of the trace ring and of a
# benchmark's spans, in the order the phases ended; a process has some ten, and
# none after the steady-state watermark (a compile then is a retrace, not set-up)
_SETUP_PHASES: List[Tuple[str, float, float]] = []


class setup_phase:
    """One phase of the process's set-up, as a context manager or a decorator.

    Appends ``(name, start, end)`` to the record :func:`process_stats` returns
    (``setup_phases``, and ``setup_seconds`` by name) and opens the span
    ``setup.<name>``, so that with a tracer configured (``SHEEPRL_TPU_TRACE``)
    set-up lies in the exported trace, and in a profiler capture as
    ``sheeprl.setup.<name>``. Phases nest (``build_agent.init`` inside
    ``build_agent``); the union of the intervals is the program's share of
    set-up. Two clock reads and a list row a phase: nothing on a step's path."""

    __slots__ = ("name", "_span", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "setup_phase":
        self._span = trace.span("setup." + self.name)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> bool:
        t1 = time.perf_counter()
        self._span.__exit__(*exc)
        record_setup_phase(self.name, self._t0, t1, span=False)
        return False

    def __call__(self, fun: Callable) -> Callable:
        name = self.name

        @functools.wraps(fun)
        def phased(*args: Any, **kwargs: Any) -> Any:
            with setup_phase(name):
                return fun(*args, **kwargs)

        return phased


def record_setup_phase(name: str, start: float, end: float, span: bool = True) -> None:
    """The one writer of the set-up record, for a phase its caller timed itself
    (the package's import, which begins before this module exists; a guarded
    compile, whose spans are its own): with ``span`` it is also the span
    ``setup.<name>``. Nothing is kept after :func:`mark_steady`."""
    if _STEADY:
        return
    _SETUP_PHASES.append((name, start, end))
    if span:
        trace.add_span("setup." + name, start, end, clock="perf")


class RetraceError(RuntimeError):
    """Raised under ``compile.guard.policy=halt`` when a guarded function
    retraces after the steady-state watermark."""


# --------------------------------------------------------------------------- #
# Abstract signatures
# --------------------------------------------------------------------------- #


def _leaf_sig(x: Any) -> Tuple:
    """(shape, dtype, weak_type) of one argument leaf; ``jax.ShapeDtypeStruct``
    warmup specs and real arrays produce identical entries by construction."""
    if isinstance(x, (bool, int, float, complex)):
        return ((), np.result_type(type(x)).name, True)
    shape = tuple(getattr(x, "shape", ()))
    dtype = getattr(x, "dtype", None)
    return (shape, np.dtype(dtype).name if dtype is not None else type(x).__name__,
            bool(getattr(x, "weak_type", False)))


def abstract_signature(args: Tuple, kwargs: Dict[str, Any]) -> Tuple:
    """Hashable abstract call signature: pytree structure + per-leaf
    (shape, dtype, weak_type). Shardings are not part of it: the warmup specs
    carry the placement the call will use (:func:`spec_like`), and a committed
    argument that disagrees with it is a counted fallback in ``__call__``."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (tuple(_leaf_sig(leaf) for leaf in leaves), treedef)


def _routing_key(sig: Tuple) -> Tuple:
    """AOT-lookup key: the signature with weak_type erased. Compiled executables
    accept weak- and strong-typed inputs interchangeably (verified both
    directions), and spec-derived warmup signatures are always strong-typed
    while e.g. ``jnp.full(..., 2.0)`` products are weak — routing on the full
    signature would spuriously miss."""
    leaves, treedef = sig
    return (tuple((s, d, False) for s, d, _w in leaves), treedef)


def signature_diff(old: Optional[Tuple], new: Tuple) -> str:
    """Human-readable per-leaf diff between two abstract signatures."""
    if old is None:
        return "first trace (no previous signature)"
    old_leaves, old_def = old
    new_leaves, new_def = new
    if old_def != new_def:
        return f"pytree structure changed: {old_def} -> {new_def}"
    changes = []
    for i, (a, b) in enumerate(zip(old_leaves, new_leaves)):
        if a != b:
            changes.append(f"leaf[{i}]: {a} -> {b}")
    return "; ".join(changes) if changes else "signatures identical (jit cache dropped?)"


def spec_like(x: Any) -> Any:
    """``jax.ShapeDtypeStruct`` mirroring one concrete array: shape, dtype and,
    for a COMMITTED array, its sharding, so AOT compiles for the placement the
    call will use. That includes a single device that is not the default one
    (the host-CPU player on a TPU host): a compiled executable rejects committed
    arguments placed anywhere else. Uncommitted arrays and host values carry no
    sharding, exactly as ``jit`` treats them: they follow the committed operands.
    """
    sharding = x.sharding if isinstance(x, jax.Array) and x.committed else None
    return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype, sharding=sharding)


def specs_of(tree: Any) -> Any:
    """Pytree of :func:`spec_like` specs for a pytree of arrays."""
    return jax.tree_util.tree_map(spec_like, tree)


def stacked_specs(tree: Any, n: int, mesh: Any = None, axis: str = "data") -> Any:
    """AOT warmup specs for ``tree`` stacked along a NEW leading axis of size ``n``.

    The population trainer (envs/ingraph/population.py) trains N PBT members as
    one vmapped program over member-stacked params/opt-state/carry pytrees. The
    stacked arrays are expensive to materialize (N copies of the model), so the
    background AOT warmup wants their specs *before* the stack exists — this
    derives them from a single member's live values (or specs). With ``mesh``
    given (>1 device), every leaf is annotated with the population-axis
    sharding ``P(axis)`` so the compile targets the mesh-sharded placements.
    """

    def one(x: Any) -> jax.ShapeDtypeStruct:
        sharding = None
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            sharding = NamedSharding(mesh, PartitionSpec(axis))
        return jax.ShapeDtypeStruct((int(n),) + tuple(x.shape), x.dtype, sharding=sharding)

    return jax.tree_util.tree_map(one, tree)


# --------------------------------------------------------------------------- #
# The retrace guard
# --------------------------------------------------------------------------- #


class GuardedFn:
    """A ``jax.jit``-compatible callable with trace accounting and AOT routing.

    Calls whose abstract signature matches a warmed AOT executable go straight
    to it (zero tracing). The executable is picked by the few leaves that tell
    the registered ones apart (none, with one registered) and checks the rest of
    the call itself, refusing one it was not compiled for before anything runs;
    a refused call takes the full route, the whole abstract signature and its
    lookup. Everything else goes through the jitted path, where a
    side-effecting hook inside the wrapped function counts actual traces. Any
    trace after the first compile of this function is a *retrace*: the
    signature diff is logged, and after :func:`mark_steady` the configured
    policy applies (``warn`` logs, ``halt`` raises :class:`RetraceError`).
    """

    def __init__(self, fun: Callable, name: Optional[str] = None, **jit_kwargs: Any):
        self.fun = fun
        self.name = name or getattr(fun, "__name__", "<fn>")
        self._jit_kwargs = dict(jit_kwargs)
        self._aot: Dict[Tuple, Any] = {}
        # exact model FLOPs per AOT executable, from cost_analysis() at
        # compile time (telemetry: Time/mfu is computed from these, never
        # hand-derived). Keyed like _aot; last_step_flops is the newest.
        self._aot_flops: Dict[Tuple, float] = {}
        # what a call's executable is picked by (_reselect), rebuilt whenever _aot changes
        self._selector: Tuple[Any, Any] = (None, None)
        self.last_step_flops: Optional[float] = None
        # bytes accessed per call, same provenance (stats() and the program ledger)
        self.last_step_bytes: Optional[float] = None
        self.flops_dispatched = 0.0
        # warmup jobs queued for this fn but not yet compiled (threading.Events,
        # set by the AOTWarmup thread): callers racing the warmup wait for them
        # instead of redundantly tracing the same signature on the hot path
        self._aot_pending: List[threading.Event] = []
        self._trace_count = 0
        self._lower_mark = 0.0  # this thread's closed-trace seconds when the last trace began
        self.calls = 0
        self.retraces = 0
        self.aot_compiles = 0
        self.aot_fallbacks = 0
        # compile_seconds: trace + lower + compile (or load from the persistent
        # cache), AOT and jit path alike; lower_seconds: the trace-and-lower part,
        # which no cache saves (on the jit path, what JAX's own events timed)
        self.compile_seconds = 0.0
        self.lower_seconds = 0.0
        # host time of the calls that compiled nothing, split at the point where
        # the executable is known: finding it (the selector's pick; on a miss also
        # a refused dispatch, abstract_signature over every leaf, the lookup, a
        # wait for a pending warmup) and the executable's own call
        self.route_seconds = 0.0
        self.execute_seconds = 0.0
        # routed calls the selector's pick served / that took the full route
        self.route_hits = 0
        self.route_misses = 0
        # span names built once: the disabled tracer's fast path must not format strings
        self._span_names = {k: f"{self.name}.{k}" for k in ("route", "execute", "lower", "compile")}
        # each compile is a set-up phase of its own, spanned by `lower` and `compile`
        # (AOT) or by the `execute` of the call that traced (jit)
        self._phase = f"compile.{self.name}"
        self.first_call_s: Optional[float] = None  # seconds since module import
        self.last_signature: Optional[Tuple] = None
        self.last_diff: Optional[str] = None
        self._had_any_compile = False

        def _traced(*args, **kwargs):
            # runs ONLY while jax traces the function (retraces included);
            # executed computations never re-enter the Python body. The trace
            # opened around it closes later, so this thread's total of closed
            # traces is still the one from before the call
            self._trace_count += 1
            self._lower_mark = _thread_trace_seconds()
            return fun(*args, **kwargs)

        try:
            _traced.__name__ = f"guarded[{self.name}]"
            _traced.__wrapped__ = fun  # jit resolves static_argnames via inspect.signature
        except Exception:
            pass
        self._jitted = jax.jit(_traced, **jit_kwargs)
        with _LOCK:
            _REGISTRY.append(self)

    # ----- properties -----------------------------------------------------------
    @property
    def traces(self) -> int:
        """Traces through the jitted call path (AOT warmup compiles excluded)."""
        return self._trace_count

    def stats(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "calls": self.calls,
            "traces": self.traces,
            "retraces": self.retraces,
            "aot_compiles": self.aot_compiles,
            "aot_fallbacks": self.aot_fallbacks,
            "compile_seconds": self.compile_seconds,
            "lower_seconds": self.lower_seconds,
            "route_seconds": self.route_seconds,
            "execute_seconds": self.execute_seconds,
            "route_hits": self.route_hits,
            "route_misses": self.route_misses,
            "first_call_s": self.first_call_s,
            "flops_dispatched": self.flops_dispatched,
            "step_flops": self.last_step_flops,
            "step_bytes": self.last_step_bytes,
        }

    # ----- AOT ------------------------------------------------------------------
    def aot_compile(self, *specs: Any, **kwspecs: Any) -> Any:
        """``jit(fun).lower(*specs).compile()`` and register the executable under
        the specs' abstract signature; matching calls then never trace."""
        sig = abstract_signature(specs, kwspecs)
        t0 = time.perf_counter()
        with trace.span(self._span_names["lower"]):
            lowered = jax.jit(self.fun, **self._jit_kwargs).lower(*specs, **kwspecs)
        t_lowered = time.perf_counter()
        with trace.span(self._span_names["compile"]):
            exe = lowered.compile()
        t1 = time.perf_counter()
        dt = t1 - t0
        record_setup_phase(self._phase, t0, t1, span=False)  # its spans are `lower` and `compile`
        flops = _cost_flops(exe)
        bytes_accessed = _cost_bytes(exe)
        _record_program(self, lowered, exe, dt)
        with _LOCK:
            self._aot[_routing_key(sig)] = exe
            if flops is not None:
                self._aot_flops[_routing_key(sig)] = flops
                self.last_step_flops = flops
            self._reselect()
            if bytes_accessed is not None:
                self.last_step_bytes = bytes_accessed
            self.aot_compiles += 1
            self.compile_seconds += dt
            self.lower_seconds += t_lowered - t0
            self._had_any_compile = True
            self.last_signature = sig
        _logger.debug("[compile] AOT %s compiled in %.3fs", self.name, dt)
        return exe

    def aot_ready(self, *specs: Any, **kwspecs: Any) -> bool:
        """True when an AOT executable is registered for the specs' abstract
        signature — the serve readiness probe: a server only advertises ready
        once every bucket it may route to dispatches without tracing."""
        sig = abstract_signature(specs, kwspecs)
        with _LOCK:
            return _routing_key(sig) in self._aot

    def aot_executables(self) -> List[Any]:
        """The live AOT executables (``jax.stages.Compiled``): what a smoke
        reads to see WHERE the program runs (``input_shardings``, ``args_info``)
        — a dispatch through one of these cannot have run anywhere else."""
        with _LOCK:
            return list(self._aot.values())

    # ----- call path ------------------------------------------------------------
    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        self.calls += 1
        sig: Optional[Tuple] = None
        t0 = time.perf_counter()
        if self._aot or self._aot_pending:
            with trace.span(self._span_names["route"]) as span:
                pick = self._pick(args, kwargs)
                span.set(hit=pick is not None)
                if pick is None:
                    sig, key, pick = self._route(args, kwargs)
            error = None
            if sig is None:  # the selector's pick
                ran, out = self._execute(pick, args, kwargs, t0)
                if ran:
                    self.route_hits += 1
                    return out
                with trace.span(self._span_names["route"]) as span:
                    span.set(hit=False)
                    sig, key, found = self._route(args, kwargs)
                if found is not None and found[0] is pick[0]:
                    # the executable that holds the call's signature refused
                    # it: a fault of placement or layout, not of signature
                    pick, error = None, out
                else:
                    pick = found
            if pick is not None:
                ran, out = self._execute(pick, args, kwargs, t0)
                if ran:
                    return out
                error = out
            if error is not None:
                # the compiled executable validates its inputs BEFORE it
                # runs (nothing executed, nothing donated), and that is the
                # only place a dispatch raises these types: the signature
                # models shape/dtype only, so a placement or layout the
                # specs did not carry lands here. The jitted path below
                # either serves the call or raises the real error; evict
                # the executable so later calls skip the failing dispatch.
                # Counted: a smoke asserts aot_fallbacks == 0.
                self.aot_fallbacks += 1
                with _LOCK:
                    self._aot.pop(key, None)
                    self._aot_flops.pop(key, None)
                    self._reselect()
                _logger.warning(
                    "[compile] AOT executable for '%s' rejected its inputs (%s); "
                    "falling back to JIT for this signature",
                    self.name,
                    str(error).splitlines()[0][:200],
                )
        self.route_seconds += time.perf_counter() - t0
        before = self._trace_count
        t0 = time.perf_counter()
        with trace.span(self._span_names["execute"]):
            out = self._jitted(*args, **kwargs)
        t1 = time.perf_counter()
        dt = t1 - t0
        if self._trace_count != before:
            if sig is None:
                sig = abstract_signature(args, kwargs)
            # a call that traced is compile time, not execute time; its trace and
            # lowering are what JAX's events timed on this thread meanwhile
            self._on_compile(sig, dt, _thread_trace_seconds() - self._lower_mark)
            record_setup_phase(self._phase, t0, t1, span=False)
        else:
            self.execute_seconds += dt
        if self.first_call_s is None:
            self.first_call_s = time.perf_counter() - _T0
        return out

    def _reselect(self) -> None:
        """Rebuild the selector from the registry (the caller holds ``_LOCK``):
        ``(only, groups)``, each pick an ``(executable, flops)`` pair. With one
        executable registered, ``only`` is its pick: nothing of the call is read,
        the executable checks the whole signature itself. With several, ``groups``
        maps each registered pytree structure to the flat leaf positions at which
        its routing keys differ and the picks by ``(shape, dtype)`` there."""
        picks = {key: (exe, self._aot_flops.get(key)) for key, exe in self._aot.items()}
        if len(picks) <= 1:
            self._selector = (next(iter(picks.values()), None), None)
            return
        by_treedef: Dict[Any, List[Tuple]] = {}
        for key in picks:
            by_treedef.setdefault(key[1], []).append(key)
        groups = {}
        for treedef, keys in by_treedef.items():
            positions = tuple(i for i in range(len(keys[0][0])) if len({key[0][i] for key in keys}) > 1)
            groups[treedef] = (positions, {tuple(key[0][i][:2] for i in positions): picks[key] for key in keys})
        self._selector = (None, groups)

    def _pick(self, args: Tuple, kwargs: Dict[str, Any]) -> Optional[Tuple[Any, Optional[float]]]:
        """The selector's ``(executable, flops)`` for a call, or None where it
        names none; only the leaves that tell the executables apart are read."""
        only, groups = self._selector
        if groups is None:
            return only
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        group = groups.get(treedef)
        if group is None:
            return None
        positions, picks = group
        return picks.get(tuple(_leaf_sig(leaves[i])[:2] for i in positions))

    def _route(self, args: Tuple, kwargs: Dict[str, Any]) -> Tuple[Tuple, Tuple, Any]:
        """The full route: ``(signature, routing key, pick or None)`` from the
        whole abstract signature, its lookup and a wait for pending warmups."""
        self.route_misses += 1
        sig = abstract_signature(args, kwargs)
        key = _routing_key(sig)
        exe = self._aot.get(key)
        if exe is None and self._aot_pending:
            # a background warmup for this fn is (probably) compiling the
            # executable this call needs: waiting is never slower than
            # tracing+compiling the same signature here, and keeps the
            # jit-path compile from registering as a spurious retrace
            for ev in list(self._aot_pending):
                ev.wait(timeout=600.0)
            self._aot_pending = []
            exe = self._aot.get(key)
        return sig, key, None if exe is None else (exe, self._aot_flops.get(key))

    def _execute(self, pick: Tuple[Any, Optional[float]], args: Tuple, kwargs: Dict[str, Any],
                 t0: float) -> Tuple[bool, Any]:
        """Call a registered executable: ``(True, outputs)``, or ``(False, error)``
        where it refused the call (``TypeError``/``ValueError``, raised before
        anything runs or is donated). The route's time runs from ``t0``."""
        exe, flops = pick
        t_routed = time.perf_counter()
        try:
            with trace.span(self._span_names["execute"]):
                out = exe(*args, **kwargs)
        except (TypeError, ValueError) as e:
            return False, e
        t1 = time.perf_counter()
        self.route_seconds += t_routed - t0
        self.execute_seconds += t1 - t_routed
        if flops is not None:
            self.flops_dispatched += flops
        if self.first_call_s is None:
            self.first_call_s = t1 - _T0
        return True, out

    def _on_compile(self, sig: Tuple, dt: float, lower_dt: float) -> None:
        with _LOCK:
            self.compile_seconds += dt
            self.lower_seconds += lower_dt
            is_retrace = self._had_any_compile
            self._had_any_compile = True
            prev = self.last_signature
            self.last_signature = sig
            if is_retrace:
                self.retraces += 1
                self.last_diff = signature_diff(prev, sig)
            policy = _GUARD_POLICY
            steady = _STEADY
        if not is_retrace or policy == "off":
            return
        msg = (
            f"[compile] retrace #{self.retraces} of '{self.name}' "
            f"({dt:.3f}s{' after steady-state watermark' if steady else ''}): {self.last_diff}"
        )
        _logger.warning(msg)
        if steady and policy == "halt":
            raise RetraceError(msg)


def _record_program(gfn: "GuardedFn", lowered: Any, exe: Any, dt: float) -> None:
    """Feed the compiled-program observatory (telemetry/programs.py) with the
    (lowered, compiled) pair of an AOT compile: HLO fingerprint, cost/memory
    analyses, sharding specs, donation map, compile wall-time. Lazily imported
    and failure-proof — the ledger is telemetry and must never take down (or
    even slow past compile time) a compile that succeeded."""
    try:
        from sheeprl_tpu.core.failpoints import FailpointError
        from sheeprl_tpu.telemetry import programs as tel_programs
    except Exception:  # pragma: no cover - a broken telemetry install
        return
    try:
        tel_programs.record(
            gfn.name,
            lowered=lowered,
            compiled=exe,
            compile_seconds=dt,
            jit_kwargs=gfn._jit_kwargs,
        )
    except FailpointError:
        raise  # a chaos drill injected here on purpose; let the caller's
        # hardening (AOTWarmup's best-effort job loop) absorb it
    except Exception:
        pass


def _cost_flops(exe: Any) -> Optional[float]:
    """Model FLOPs from a compiled executable's own cost model, or None where
    the backend reports none. Never raises: FLOPs accounting is telemetry and
    must not take down a compile that otherwise succeeded."""
    try:
        cost = exe.cost_analysis()
    except Exception:
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return None
    try:
        flops = float(cost.get("flops", 0.0))
    except (AttributeError, TypeError, ValueError):
        return None
    return flops if flops > 0 else None


def _cost_bytes(exe: Any) -> Optional[float]:
    """``bytes accessed`` from a compiled executable's cost model, or None.
    Same never-raise contract as :func:`_cost_flops`."""
    try:
        cost = exe.cost_analysis()
    except Exception:
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return None
    try:
        nbytes = float(cost.get("bytes accessed", 0.0))
    except (AttributeError, TypeError, ValueError):
        return None
    return nbytes if nbytes > 0 else None


def guarded_jit(fun: Callable, name: Optional[str] = None, **jit_kwargs: Any) -> GuardedFn:
    """Drop-in ``jax.jit`` replacement returning a :class:`GuardedFn`."""
    return GuardedFn(fun, name=name, **jit_kwargs)


def step_flops(name: str) -> Optional[float]:
    """Per-call FLOPs of the newest AOT executable warmed for ``name``
    (cost_analysis at compile time), or None when it never AOT-compiled —
    the lookup Time/mfu rows are computed from."""
    gfn = find(name)
    return gfn.last_step_flops if gfn is not None else None


def step_bytes(name: str) -> Optional[float]:
    """Per-call ``bytes accessed`` of the newest AOT executable warmed for
    ``name``, or None when it never AOT-compiled."""
    gfn = find(name)
    return gfn.last_step_bytes if gfn is not None else None


def find(name: str) -> Optional[GuardedFn]:
    """The most recently created guarded function with ``name`` (fresh train
    loops create fresh instances; tests and bench want the latest run's)."""
    with _LOCK:
        for gfn in reversed(_REGISTRY):
            if gfn.name == name:
                return gfn
    return None


def release_executables() -> None:
    """Drop every compiled executable this process pins: each guarded
    function's AOT registry (this module keeps every instance alive for its
    counters) and, through ``jax.clear_caches``, the jit caches. Counters stay;
    a later call compiles again as a first trace, cheaply through the
    persistent cache. For a long-lived process that runs many short runs (the
    test suite): XLA:CPU mmaps each executable, and thousands of them cross
    ``vm.max_map_count``."""
    with _LOCK:
        for gfn in _REGISTRY:
            gfn._aot.clear()
            gfn._aot_flops.clear()
            gfn._reselect()
            gfn._had_any_compile = False
            gfn.last_signature = None
    jax.clear_caches()


def process_stats() -> Dict[str, Any]:
    """Totals across every guarded function plus the persistent-cache counters,
    the count of AOT warmup jobs that raised, JAX's compile durations of the
    whole process (``trace_seconds``, ``backend_compile_seconds``,
    ``cache_retrieval_seconds``, each with a ``*_count``) and the set-up record:
    ``setup_phases``, the ``(name, start, end)`` of every :class:`setup_phase`,
    and ``setup_seconds``, their lengths summed by name."""
    with _LOCK:
        fns = list(_REGISTRY)
        cache = dict(_CACHE_COUNTS)
        jax_totals = dict(_JAX_TOTALS)
        warmup_errors = _WARMUP_ERRORS
    phases = list(_SETUP_PHASES)
    totals = {
        "calls": 0,
        "traces": 0,
        "retraces": 0,
        "aot_compiles": 0,
        "aot_fallbacks": 0,
        "route_hits": 0,
        "route_misses": 0,
        "compile_seconds": 0.0,
        "lower_seconds": 0.0,
        "flops_dispatched": 0.0,
    }
    per_fn = {}
    for gfn in fns:
        s = gfn.stats()
        per_fn[s["name"]] = s
        for k in totals:
            totals[k] += s[k]
    totals.update(cache)
    totals.update(jax_totals)
    totals["warmup_errors"] = warmup_errors
    totals["functions"] = per_fn
    seconds: Dict[str, float] = {}
    for name, start, end in phases:
        seconds[name] = seconds.get(name, 0.0) + (end - start)
    totals["setup_phases"] = phases
    totals["setup_seconds"] = seconds
    return totals


def drain_compile_counters(aggregator: Optional[Any]) -> Dict[str, float]:
    """Fold the delta since the last drain into the aggregator (log-boundary
    hook, same shape as ``resilience.drain_env_counters``). Always updates the
    registered ``Compile/*`` keys — an explicit 0 in the logs is the signal
    that steady state held."""
    totals = process_stats()
    current = {
        "Compile/retraces": float(totals["retraces"]),
        "Compile/cache_hits": float(totals["cache_hits"]),
        "Compile/cache_misses": float(totals["cache_misses"]),
        "Time/compile_seconds": float(totals["compile_seconds"]),
    }
    with _LOCK:
        delta = {k: v - _DRAINED.get(k, 0.0) for k, v in current.items()}
        _DRAINED.update(current)
    if aggregator is not None and not getattr(aggregator, "disabled", False):
        for k, v in delta.items():
            if k in aggregator:
                aggregator.update(k, v)
    return delta


# --------------------------------------------------------------------------- #
# AOT warmup
# --------------------------------------------------------------------------- #


class AOTWarmup:
    """Background-thread AOT compiler for a run's jitted entry points.

    Register (guarded_fn, specs) jobs — or arbitrary callables — then
    ``start()``: compilation overlaps env reset / first-rollout collection /
    buffer allocation on the main thread. ``wait()`` before the first guarded
    call that must not trace. Warmup is best-effort: a failed job logs a
    warning and the entry point falls back to JIT-on-first-call.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)
        self._jobs: List[Tuple[Any, Tuple, Dict, Optional[threading.Event]]] = []
        self._thread: Optional[threading.Thread] = None
        self._done = threading.Event()
        self.errors: List[Tuple[str, BaseException]] = []
        if not self.enabled:
            self._done.set()

    def add(self, gfn: GuardedFn, *specs: Any, **kwspecs: Any) -> None:
        """Queue ``gfn.aot_compile(*specs, **kwspecs)``. The fn is marked
        pending so a racing call waits for this compile instead of tracing."""
        if self.enabled:
            if not isinstance(gfn, GuardedFn):
                # some act paths hand back a plain jitted callable (e.g. the
                # device-rollout composition); warmup is best-effort, skip it
                _logger.debug("[compile] skipping AOT warmup of non-guarded %r", gfn)
                return
            ev = threading.Event()
            gfn._aot_pending.append(ev)
            self._jobs.append((gfn, specs, kwspecs, ev))

    def add_task(self, task: Callable[[], Any], name: str = "task") -> None:
        """Queue an arbitrary warmup callable (e.g. metric-drain precompiles)."""
        if self.enabled:
            self._jobs.append((None, (task, name), {}, None))

    def start(self) -> "AOTWarmup":
        if not self.enabled or not self._jobs:
            self._done.set()
            return self
        self._thread = threading.Thread(target=self._run, name="sheeprl-aot-warmup", daemon=True)
        self._thread.start()
        return self

    def _run(self) -> None:
        global _WARMUP_ERRORS
        for gfn, specs, kwspecs, ev in self._jobs:
            try:
                if gfn is None:
                    task, _name = specs
                    task()
                else:
                    gfn.aot_compile(*specs, **kwspecs)
            except Exception as e:  # warmup must never kill the run
                name = specs[1] if gfn is None else gfn.name
                self.errors.append((name, e))
                with _LOCK:
                    _WARMUP_ERRORS += 1
                _logger.warning("[compile] AOT warmup of '%s' failed (%s: %s); falling back "
                                "to JIT on first call", name, type(e).__name__, e)
            finally:
                if ev is not None:
                    ev.set()
        self._done.set()

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until every queued warmup compile finished (cheap once done: the
        set-up phase ``aot_warmup`` is the first wait that found work left)."""
        if self._done.is_set():
            return True
        with setup_phase("aot_warmup"):
            return self._done.wait(timeout)


# --------------------------------------------------------------------------- #
# Canonical shapes: pow-2 bucketing + padded stacking
# --------------------------------------------------------------------------- #


def pow2_bucket(n: int, minimum: int = 1) -> int:
    """Smallest power of two >= max(n, minimum): a drifting count maps onto
    O(log) distinct compiled shapes instead of one compile per value."""
    n = max(int(n), int(minimum), 1)
    bucket = 1
    while bucket < n:
        bucket *= 2
    return bucket


def bucketed_pad(
    sequences: Dict[str, List[np.ndarray]],
    lengths: Sequence[int],
    length: int,
    dtype=np.float32,
) -> Dict[str, np.ndarray]:
    """Stack ragged per-key chunk lists ``[t_i, ...]`` into ``[length, W, ...]``
    arrays plus a ``mask`` ``[length, W, 1]``, with W = :func:`pow2_bucket` of
    the chunk count. Zero-padded rows/columns carry mask 0, so losses ignore
    them and the jitted consumer sees a bounded set of shapes."""
    n_seq = len(lengths)
    if n_seq == 0:
        raise ValueError("bucketed_pad needs at least one sequence")
    bucket = pow2_bucket(n_seq)
    out: Dict[str, np.ndarray] = {}
    for k, chunks in sequences.items():
        if len(chunks) != n_seq:
            raise ValueError(f"key '{k}' has {len(chunks)} chunks for {n_seq} lengths")
        sample_shape = chunks[0].shape[1:]
        arr = np.zeros((length, bucket, *sample_shape), dtype=dtype)
        for i, c in enumerate(chunks):
            arr[: c.shape[0], i] = c
        out[k] = arr
    mask = np.zeros((length, bucket, 1), dtype=dtype)
    for i, ln in enumerate(lengths):
        mask[:ln, i] = 1.0
    out["mask"] = mask
    return out


# JAX's compile events are counted from the package's import on, a compile the
# caller makes before any run is configured included
install_cache_listeners()
