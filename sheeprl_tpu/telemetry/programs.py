"""Compiled-program observatory: per-compile XLA cost/memory/sharding ledger.

PR 12's tracer records *when* a step ran; nothing recorded *what program* XLA
actually built for it. This module closes that gap: every AOT compile through
``core/compile.py`` (``GuardedFn.aot_compile``, and therefore every
``AOTWarmup`` job, serve bucket warmup, and fused-trainer program) calls
:func:`record` with the lowered + compiled pair, and the observatory captures

- a stable **fingerprint** — sha256 of the lowered StableHLO text, so "did
  this refactor change the program XLA sees?" is one string compare;
- ``cost_analysis()`` **FLOPs / bytes-accessed** (the same numbers Time/mfu
  is computed from);
- the ``memory_analysis()`` **HBM breakdown** (argument / output / temp /
  generated-code / alias bytes, plus their sum as ``peak_bytes``);
- **input/output sharding specs** and the donation map — the observables the
  mesh-aware sharding work (ROADMAP item 2) will be reviewed against;
- the **collective audit** — the optimized HLO is scanned for collective ops
  (``all-reduce``/``all-gather``/``reduce-scatter``/…), split into async
  ``*-start``/``*-done`` pairs (overlappable with compute by the latency-hiding
  scheduler) vs plain sync forms (exposed), with total and exposed bytes (a
  count; exposed collective TIME comes from a profiler trace on the chip, not
  from an assumed link rate); the ``diff`` CLI flags a collective that
  de-async'd (async pair -> sync op) or grew its bytes as a regression;
- compile **wall-time**.

Rows are schema-versioned JSON lines appended to a per-run ``programs.jsonl``
stamped with the PR-12 trace id and the git SHA, so a ledger row is joinable
with spans, health events, and bench records. Recording happens ONLY at
compile time: warm steps never touch this module (proved by the
``jax.transfer_guard`` test in ``tests/test_utils/test_programs.py``), so the
steady-state cost of carrying the observatory is zero.

Three consumers:

- the in-memory registry feeds :func:`gauges` (``Program/<name>/...`` rows)
  into the metrics fabric, so serve replicas expose per-program peak-HBM and
  compile-seconds through the Prometheus ``{"op": "metrics"}`` exposition;
- ``python -m sheeprl_tpu.telemetry.programs diff <runA> <runB>`` compares
  two ledgers (new/removed programs, fingerprint churn, memory/FLOP deltas,
  sharding-spec changes) with text and ``--json`` output, exiting 1 when a
  memory regression or sharding change is flagged;
- ``bench.py`` stamps its records into ``benchmarks/ledger.jsonl`` and
  ``bench.py --check-regressions`` runs the cross-run sentinel over them.

Activation mirrors :mod:`sheeprl_tpu.telemetry.trace`: the
``SHEEPRL_TPU_PROGRAMS`` env var (a ledger path, read once at import so
subprocesses inherit the parent's ledger) wins over the per-run default the
train loops install under ``<log_dir>/telemetry/programs.jsonl``. Without
either, compiles are still captured in memory for the gauges — only the
JSONL write is skipped. Every capture step is failure-proof: a backend that
lacks ``memory_analysis`` (CPU reports it, some don't), an un-text-able
lowering, or an unwritable path degrades to nulls/in-memory-only, never to a
failed compile.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from sheeprl_tpu.core import failpoints
from sheeprl_tpu.telemetry import trace

ENV_VAR = "SHEEPRL_TPU_PROGRAMS"

#: Bump on any row-shape change; readers skip rows from the future.
SCHEMA_VERSION = 1

#: memory_analysis() attribute -> row key in the ``memory`` breakdown.
_MEMORY_FIELDS = {
    "argument_size_in_bytes": "argument_bytes",
    "output_size_in_bytes": "output_bytes",
    "temp_size_in_bytes": "temp_bytes",
    "generated_code_size_in_bytes": "generated_code_bytes",
    "alias_size_in_bytes": "alias_bytes",
}

_lock = threading.Lock()
_path: Optional[str] = None
# newest row per program name (the gauges read this; bounded by the number of
# distinct compiled entry points, not by compile count)
_latest: Dict[str, Dict[str, Any]] = {}
_rows_recorded = 0
_write_errors = 0
_git_sha: Optional[str] = None
_git_sha_resolved = False
# ambient key/values stamped into every subsequent row (e.g. the active
# fabric.xla_profile); process-wide like the ledger path itself
_context: Dict[str, Any] = {}


# --------------------------------------------------------------------------- #
# configuration / lifecycle
# --------------------------------------------------------------------------- #


def configure(path: Optional[str], *, mirror_env: bool = True) -> Optional[str]:
    """Point the ledger at ``path`` (None disables the JSONL write; in-memory
    capture and the gauges keep working). Mirrors the path into
    ``os.environ[SHEEPRL_TPU_PROGRAMS]`` so subprocesses spawned after this
    point (bench workers, serve children, smoke drills) append to the SAME
    per-run ledger — the trace-id inheritance scheme, applied to programs."""
    global _path
    with _lock:
        _path = os.path.abspath(path) if path else None
    if mirror_env:
        if _path:
            os.environ[ENV_VAR] = _path
        else:
            os.environ.pop(ENV_VAR, None)
    return _path


def configure_default(path: Optional[str]) -> Optional[str]:
    """Install ``path`` only when no ledger is configured yet — the train
    loops' per-run default must not sever a parent-pinned ``SHEEPRL_TPU_PROGRAMS``
    (an orchestrator collecting every child's compiles into one ledger)."""
    with _lock:
        if _path is not None:
            return _path
    return configure(path)


def configure_from_env(environ: Optional[Dict[str, str]] = None) -> Optional[str]:
    spec = (environ if environ is not None else os.environ).get(ENV_VAR)
    if not spec:
        return None
    return configure(spec, mirror_env=False)


def ledger_path() -> Optional[str]:
    with _lock:
        return _path


def reset() -> None:
    """Drop the in-memory registry and counters and detach the ledger (tests)."""
    global _latest, _rows_recorded, _write_errors, _path, _context
    with _lock:
        _latest = {}
        _rows_recorded = 0
        _write_errors = 0
        _path = None
        _context = {}
    os.environ.pop(ENV_VAR, None)


def set_context(**kv: Any) -> Dict[str, Any]:
    """Merge ambient key/values into every row recorded from now on (``None``
    deletes a key). The overlap layer stamps ``xla_profile`` here so a ledger
    row says which XLA scheduling profile the program compiled under."""
    global _context
    with _lock:
        merged = dict(_context)
        for k, v in kv.items():
            if v is None:
                merged.pop(k, None)
            else:
                merged[k] = v
        _context = merged
        return dict(merged)


# --------------------------------------------------------------------------- #
# capture
# --------------------------------------------------------------------------- #


def record(
    name: str,
    *,
    lowered: Any = None,
    compiled: Any = None,
    compile_seconds: Optional[float] = None,
    jit_kwargs: Optional[Dict[str, Any]] = None,
) -> Optional[Dict[str, Any]]:
    """Capture one compiled program. Called by ``GuardedFn.aot_compile`` with
    the (lowered, compiled) pair — i.e. once per XLA compile, never per step.
    Never raises: the observatory must not take down a compile that otherwise
    succeeded. Returns the row (also when the JSONL write is disabled)."""
    try:
        failpoints.failpoint("telemetry.program_record", program=name)
        row = _build_row(name, lowered, compiled, compile_seconds, jit_kwargs)
    except failpoints.FailpointError:
        raise  # chaos drills opt in explicitly; only they see the error
    except Exception:
        return None
    global _rows_recorded
    with _lock:
        _latest[name] = row
        _rows_recorded += 1
        path = _path
    if path:
        _append(path, row)
    return row


def _build_row(
    name: str,
    lowered: Any,
    compiled: Any,
    compile_seconds: Optional[float],
    jit_kwargs: Optional[Dict[str, Any]],
) -> Dict[str, Any]:
    cost = _cost_dict(compiled)
    memory = _memory_dict(compiled)
    in_sh, out_sh = _sharding_lists(compiled)
    with _lock:
        ctx = dict(_context)
    row: Dict[str, Any] = {
        "schema": SCHEMA_VERSION,
        "time": time.time(),
        "name": str(name),
        "fingerprint": _fingerprint(lowered),
        "compile_seconds": float(compile_seconds) if compile_seconds is not None else None,
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "memory": memory,
        "collective": _collective_dict(compiled),
        "input_shardings": in_sh,
        "output_shardings": out_sh,
        "donation": _donation(jit_kwargs),
        "backend": _backend_name(),
        "num_devices": _device_count(),
        "trace_id": trace.current_trace_id() or None,
        "git_sha": _git_head(),
        "context": ctx or None,
    }
    return row


def _fingerprint(lowered: Any) -> Optional[str]:
    """sha256 of the lowered StableHLO text: identical programs hash identically
    across recompiles and processes (module names in the text are stable for a
    given entry point), and any op-level change churns the hash."""
    if lowered is None:
        return None
    try:
        text = lowered.as_text()
    except Exception:
        return None
    return hashlib.sha256(text.encode("utf-8", errors="replace")).hexdigest()[:24]


def _cost_dict(compiled: Any) -> Dict[str, float]:
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not cost:
        return {}
    out: Dict[str, float] = {}
    for key in ("flops", "bytes accessed"):
        try:
            v = float(cost.get(key))
            if v >= 0:
                out[key] = v
        except (AttributeError, TypeError, ValueError):
            continue
    # XLA omits 'flops' for zero-arithmetic programs (pure copies): that is a
    # true 0, distinct from "cost analysis unavailable" (null)
    out.setdefault("flops", 0.0)
    return out


# Longest-first so `all-reduce-start` wins over `all-reduce`; anchored on the
# HLO statement position (opcode immediately followed by its operand paren, not
# preceded by a `%`/word char, which would make it an operand *reference* like
# `%all-reduce.5` or part of a fusion name).
_COLLECTIVE_OPS = (
    "all-reduce-start",
    "all-reduce-done",
    "all-gather-start",
    "all-gather-done",
    "collective-permute-start",
    "collective-permute-done",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
    "all-reduce",
    "all-gather",
)
_COLLECTIVE_RE = re.compile(
    r"(?<![\w%.-])(" + "|".join(re.escape(op) for op in _COLLECTIVE_OPS) + r")\("
)
_SHAPE_RE = re.compile(
    r"\b(pred|bf16|f16|f32|f64|s8|s16|s32|s64|u8|u16|u32|u64|c64|c128)\[([0-9,]*)\]"
)
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f32": 4, "s32": 4, "u32": 4,
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "c128": 16,
}
def _shape_bytes(segment: str) -> float:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(segment):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += _DTYPE_BYTES[dtype] * n
    return total


def _collective_dict(compiled: Any) -> Optional[Dict[str, Any]]:
    """The HLO collective audit: scan the compiled program's optimized HLO for
    collective ops, splitting them into async pairs (``*-start``/``*-done`` —
    the latency-hiding scheduler can overlap these with compute) and plain sync
    forms (exposed: the step stalls for the wire). Bytes are the result-shape
    sizes of the issuing op (``-done`` ops reference the same buffer and are
    not double-counted). Returns ``None`` when the backend can't render HLO
    text — never raises."""
    if compiled is None:
        return None
    try:
        text = compiled.as_text()
    except Exception:
        return None
    if not isinstance(text, str):
        return None
    by_op: Dict[str, int] = {}
    total_bytes = 0.0
    async_pairs = 0
    sync_ops = 0
    exposed_bytes = 0.0
    for line in text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        op = m.group(1)
        by_op[op] = by_op.get(op, 0) + 1
        if op.endswith("-done"):
            continue  # the buffer was counted at its matching -start
        nbytes = _shape_bytes(line[: m.start()])
        total_bytes += nbytes
        if op.endswith("-start"):
            async_pairs += 1
        else:
            sync_ops += 1
            exposed_bytes += nbytes
    return {
        "op_count": sum(by_op.values()),
        "bytes": total_bytes,
        "async_pairs": async_pairs,
        "sync_ops": sync_ops,
        "exposed_bytes": exposed_bytes,
        "by_op": by_op,
    }


def _memory_dict(compiled: Any) -> Optional[Dict[str, float]]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out: Dict[str, float] = {}
    for attr, key in _MEMORY_FIELDS.items():
        try:
            out[key] = float(getattr(ma, attr))
        except (AttributeError, TypeError, ValueError):
            continue
    if not out:
        return None
    # live-at-once upper bound: everything the executable holds while running
    # (aliased buffers are donated inputs reused as outputs — counted once)
    out["peak_bytes"] = (
        out.get("argument_bytes", 0.0)
        + out.get("output_bytes", 0.0)
        + out.get("temp_bytes", 0.0)
        + out.get("generated_code_bytes", 0.0)
        - out.get("alias_bytes", 0.0)
    )
    return out


def _sharding_lists(compiled: Any) -> Tuple[Optional[List[str]], Optional[List[str]]]:
    def _flatten(tree: Any) -> Optional[List[str]]:
        if tree is None:
            return None
        try:
            import jax

            leaves = jax.tree_util.tree_leaves(tree)
            return [str(leaf) for leaf in leaves]
        except Exception:
            return None

    in_sh = out_sh = None
    try:
        in_sh = _flatten(getattr(compiled, "input_shardings", None))
    except Exception:
        pass
    try:
        out_sh = _flatten(getattr(compiled, "output_shardings", None))
    except Exception:
        pass
    return in_sh, out_sh


def _donation(jit_kwargs: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    if not jit_kwargs:
        return {}
    out: Dict[str, Any] = {}
    argnums = jit_kwargs.get("donate_argnums")
    if argnums is not None:
        out["argnums"] = list(argnums) if isinstance(argnums, (tuple, list)) else [argnums]
    argnames = jit_kwargs.get("donate_argnames")
    if argnames is not None:
        out["argnames"] = list(argnames) if isinstance(argnames, (tuple, list)) else [argnames]
    return out


def _backend_name() -> Optional[str]:
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return None


def _device_count() -> Optional[int]:
    try:
        import jax

        return jax.device_count()
    except Exception:
        return None


def _git_head() -> Optional[str]:
    """Short git SHA of the tree (cached; null-tolerant — a missing git binary
    or a non-repo install dir must never cost the row)."""
    global _git_sha, _git_sha_resolved
    with _lock:
        if _git_sha_resolved:
            return _git_sha
    sha: Optional[str] = None
    try:
        import subprocess

        proc = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True,
            text=True,
            timeout=10,
        )
        sha = proc.stdout.strip() or None
    except Exception:
        sha = None
    with _lock:
        _git_sha = sha
        _git_sha_resolved = True
    return sha


def _append(path: str, row: Dict[str, Any]) -> None:
    global _write_errors
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
    except OSError:
        with _lock:
            _write_errors += 1


# --------------------------------------------------------------------------- #
# read side: gauges, snapshots, ledger parsing
# --------------------------------------------------------------------------- #


def snapshot() -> List[Dict[str, Any]]:
    """Newest in-memory row per program name (sorted by name)."""
    with _lock:
        return [dict(_latest[k]) for k in sorted(_latest)]


def stats() -> Dict[str, Any]:
    with _lock:
        return {
            "programs": len(_latest),
            "rows_recorded": _rows_recorded,
            "write_errors": _write_errors,
            "ledger_path": _path,
        }


def gauges() -> Dict[str, float]:
    """Per-program footprint gauges for the metrics fabric — the serve
    ``{"op": "metrics"}`` Prometheus exposition includes these, so a scraper
    sees each replica's compiled-program HBM footprint live."""
    with _lock:
        latest = dict(_latest)
        recorded = _rows_recorded
        errors = _write_errors
    out: Dict[str, float] = {
        "Programs/recorded": float(recorded),
        "Programs/distinct": float(len(latest)),
    }
    if errors:
        out["Programs/write_errors"] = float(errors)
    for name, row in latest.items():
        mem = row.get("memory") or {}
        if mem.get("peak_bytes") is not None:
            out[f"Program/{name}/peak_hbm_bytes"] = float(mem["peak_bytes"])
        if row.get("compile_seconds") is not None:
            out[f"Program/{name}/compile_seconds"] = float(row["compile_seconds"])
        if row.get("flops") is not None:
            out[f"Program/{name}/flops"] = float(row["flops"])
        coll = row.get("collective")
        if coll and coll.get("bytes") is not None:
            out[f"Program/{name}/collective_bytes"] = float(coll["bytes"])
            out[f"Program/{name}/collective_ops"] = float(coll.get("op_count", 0))
            out[f"Program/{name}/exposed_collective_bytes"] = float(
                coll.get("exposed_bytes", 0.0)
            )
    return out


def read_ledger(path: str) -> List[Dict[str, Any]]:
    """Parse one ``programs.jsonl``; skips blank/corrupt lines and rows from a
    future schema (torn tails from a crashed run must not kill the diff)."""
    rows: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if not isinstance(row, dict) or row.get("name") is None:
                continue
            if int(row.get("schema", 0)) > SCHEMA_VERSION:
                continue
            rows.append(row)
    return rows


def _resolve_ledger(run: str) -> str:
    """Accept a ledger file OR a run directory (searched at the per-run default
    location ``<run>/telemetry/programs.jsonl``, then ``<run>/programs.jsonl``)."""
    if os.path.isfile(run):
        return run
    for candidate in (
        os.path.join(run, "telemetry", "programs.jsonl"),
        os.path.join(run, "programs.jsonl"),
    ):
        if os.path.isfile(candidate):
            return candidate
    raise FileNotFoundError(f"no programs ledger at {run!r}")


# --------------------------------------------------------------------------- #
# diff
# --------------------------------------------------------------------------- #


def _latest_by_name(rows: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    for row in rows:  # file order == append order: last row per name wins
        out[str(row["name"])] = row
    return out


def diff_ledgers(
    rows_a: List[Dict[str, Any]],
    rows_b: List[Dict[str, Any]],
    *,
    mem_threshold: float = 0.05,
    flops_threshold: float = 0.05,
) -> Dict[str, Any]:
    """Structural + footprint diff of two ledgers (A = baseline, B = candidate).

    Per program (newest row per name on each side): fingerprint churn, per-field
    HBM-breakdown deltas (a growth beyond ``mem_threshold`` is a flagged
    regression), FLOP deltas (either direction beyond ``flops_threshold`` is
    reported; growth is flagged), and sharding-spec changes (always flagged —
    an unintended resharding is the classic silent perf cliff). ``regressions``
    collects everything that should fail a gate."""
    a, b = _latest_by_name(rows_a), _latest_by_name(rows_b)
    report: Dict[str, Any] = {
        "programs_a": len(a),
        "programs_b": len(b),
        "new": sorted(set(b) - set(a)),
        "removed": sorted(set(a) - set(b)),
        "hash_churn": [],
        "memory_deltas": [],
        "flops_deltas": [],
        "collective_deltas": [],
        "sharding_changes": [],
        "regressions": [],
    }
    for name in sorted(set(a) & set(b)):
        ra, rb = a[name], b[name]
        fa, fb = ra.get("fingerprint"), rb.get("fingerprint")
        if fa and fb and fa != fb:
            report["hash_churn"].append({"name": name, "a": fa, "b": fb})
        ma, mb = ra.get("memory") or {}, rb.get("memory") or {}
        for field in sorted(set(ma) | set(mb)):
            va, vb = ma.get(field), mb.get(field)
            if va is None or vb is None:
                continue
            if va == vb:
                continue
            pct = ((vb - va) / va) if va else None
            entry = {"name": name, "field": field, "a": va, "b": vb, "pct": pct}
            grew = (vb > va * (1.0 + mem_threshold)) if va else vb > 0
            entry["regression"] = bool(grew)
            report["memory_deltas"].append(entry)
            if grew:
                report["regressions"].append(
                    f"{name}: memory.{field} {_fmt_bytes(va)} -> {_fmt_bytes(vb)}"
                    + (f" (+{pct * 100.0:.1f}%)" if pct is not None else "")
                )
        va, vb = ra.get("flops"), rb.get("flops")
        if va is not None and vb is not None and va != vb:
            pct = ((vb - va) / va) if va else None
            if pct is None or abs(pct) > flops_threshold:
                grew = vb > va
                report["flops_deltas"].append(
                    {"name": name, "a": va, "b": vb, "pct": pct, "regression": bool(grew)}
                )
                if grew:
                    report["regressions"].append(
                        f"{name}: flops {va:.3e} -> {vb:.3e}"
                        + (f" (+{pct * 100.0:.1f}%)" if pct is not None else "")
                    )
        ca, cb = ra.get("collective") or {}, rb.get("collective") or {}
        if ca and cb:
            entry: Optional[Dict[str, Any]] = None
            pa, pb = int(ca.get("async_pairs") or 0), int(cb.get("async_pairs") or 0)
            sa, sb = int(ca.get("sync_ops") or 0), int(cb.get("sync_ops") or 0)
            ba, bb = float(ca.get("bytes") or 0.0), float(cb.get("bytes") or 0.0)
            deasync = pb < pa and sb > sa
            bytes_grew = bb > ba * (1.0 + mem_threshold) if ba else bb > 0.0
            if deasync or bytes_grew or ba != bb or pa != pb or sa != sb:
                entry = {
                    "name": name,
                    "async_pairs": {"a": pa, "b": pb},
                    "sync_ops": {"a": sa, "b": sb},
                    "bytes": {"a": ba, "b": bb},
                    "deasync": bool(deasync),
                    "regression": bool(deasync or bytes_grew),
                }
                report["collective_deltas"].append(entry)
            if deasync:
                # the overlap regression the auditor exists for: a collective
                # that compiled as an async start/done pair (overlappable with
                # compute) now compiles as a plain sync op (exposed on the wire)
                report["regressions"].append(
                    f"{name}: collective de-async'd ({pa} -> {pb} async pair(s), "
                    f"{sa} -> {sb} sync op(s))"
                )
            if bytes_grew:
                pct = ((bb - ba) / ba * 100.0) if ba else None
                report["regressions"].append(
                    f"{name}: collective bytes {_fmt_bytes(ba)} -> {_fmt_bytes(bb)}"
                    + (f" (+{pct:.1f}%)" if pct is not None else "")
                )
        for io in ("input_shardings", "output_shardings"):
            sa, sb = ra.get(io), rb.get(io)
            if sa is not None and sb is not None and sa != sb:
                report["sharding_changes"].append({"name": name, "io": io, "a": sa, "b": sb})
                report["regressions"].append(f"{name}: {io} changed")
    return report


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n:.0f}B"
        n /= 1024.0
    return f"{n:.1f}GiB"


def format_diff(report: Dict[str, Any]) -> str:
    lines = [
        f"programs: {report['programs_a']} (A) vs {report['programs_b']} (B)",
    ]
    if report["new"]:
        lines.append(f"new in B: {', '.join(report['new'])}")
    if report["removed"]:
        lines.append(f"removed in B: {', '.join(report['removed'])}")
    for entry in report["hash_churn"]:
        lines.append(f"hash churn: {entry['name']} {entry['a']} -> {entry['b']}")
    for entry in report["memory_deltas"]:
        pct = f" ({entry['pct'] * 100.0:+.1f}%)" if entry.get("pct") is not None else ""
        flag = "  << REGRESSION" if entry.get("regression") else ""
        lines.append(
            f"memory {entry['name']}.{entry['field']}: "
            f"{_fmt_bytes(entry['a'])} -> {_fmt_bytes(entry['b'])}{pct}{flag}"
        )
    for entry in report["flops_deltas"]:
        pct = f" ({entry['pct'] * 100.0:+.1f}%)" if entry.get("pct") is not None else ""
        flag = "  << REGRESSION" if entry.get("regression") else ""
        lines.append(f"flops {entry['name']}: {entry['a']:.4g} -> {entry['b']:.4g}{pct}{flag}")
    for entry in report.get("collective_deltas", []):
        flag = "  << REGRESSION" if entry.get("regression") else ""
        note = " (de-async'd)" if entry.get("deasync") else ""
        lines.append(
            f"collective {entry['name']}: "
            f"async {entry['async_pairs']['a']} -> {entry['async_pairs']['b']}, "
            f"sync {entry['sync_ops']['a']} -> {entry['sync_ops']['b']}, "
            f"bytes {_fmt_bytes(entry['bytes']['a'])} -> {_fmt_bytes(entry['bytes']['b'])}"
            f"{note}{flag}"
        )
    for entry in report["sharding_changes"]:
        lines.append(
            f"sharding {entry['name']}.{entry['io']}: {entry['a']} -> {entry['b']}  << CHANGED"
        )
    if report["regressions"]:
        lines.append(f"{len(report['regressions'])} regression(s) flagged")
    else:
        lines.append("no regressions flagged")
    return "\n".join(lines)


# --------------------------------------------------------------------------- #
# CLI: python -m sheeprl_tpu.telemetry.programs diff <runA> <runB>
# --------------------------------------------------------------------------- #


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m sheeprl_tpu.telemetry.programs",
        description="compiled-program ledger tools",
    )
    sub = parser.add_subparsers(dest="command", required=True)
    d = sub.add_parser("diff", help="compare two programs.jsonl ledgers (A=baseline, B=candidate)")
    d.add_argument("run_a", help="baseline: a programs.jsonl file or a run directory")
    d.add_argument("run_b", help="candidate: a programs.jsonl file or a run directory")
    d.add_argument("--json", action="store_true", help="machine-readable report on stdout")
    d.add_argument(
        "--mem-threshold-pct",
        type=float,
        default=5.0,
        help="flag a memory field growing beyond this percentage (default 5)",
    )
    d.add_argument(
        "--flops-threshold-pct",
        type=float,
        default=5.0,
        help="report FLOP deltas beyond this percentage (default 5)",
    )
    s = sub.add_parser("show", help="print the newest row per program from one ledger")
    s.add_argument("run", help="a programs.jsonl file or a run directory")
    s.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)

    if args.command == "show":
        rows = _latest_by_name(read_ledger(_resolve_ledger(args.run)))
        if args.json:
            print(json.dumps(list(rows.values())))
        else:
            for name in sorted(rows):
                row = rows[name]
                mem = row.get("memory") or {}
                print(
                    f"{name}: fp={row.get('fingerprint')} flops={row.get('flops')} "
                    f"peak={_fmt_bytes(mem.get('peak_bytes', 0.0))} "
                    f"compile={row.get('compile_seconds')}s"
                )
        return 0

    report = diff_ledgers(
        read_ledger(_resolve_ledger(args.run_a)),
        read_ledger(_resolve_ledger(args.run_b)),
        mem_threshold=args.mem_threshold_pct / 100.0,
        flops_threshold=args.flops_threshold_pct / 100.0,
    )
    if args.json:
        print(json.dumps(report))
    else:
        print(format_diff(report))
    return 1 if report["regressions"] else 0


# Subprocesses inherit the parent's ledger through the env var, exactly like
# the tracer: reading it at import means every entry point appends to one
# per-run ledger with no plumbing.
configure_from_env()

if __name__ == "__main__":
    raise SystemExit(main())
