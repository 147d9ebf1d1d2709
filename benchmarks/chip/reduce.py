"""From a profiler trace to numbers: device busy time, the idle share, the device
operations that took most time and the longest idle gaps by what the host was
doing. ``jax.profiler.ProfileData`` reads the ``.xplane.pb``; nothing else is
needed. The arithmetic works on plain lists of intervals, so the tests check it
on a small synthetic list.

A device is a plane named ``/device:TPU:<n>``. Its busy time is the union of the
events on its ``XLA Ops`` line (the operations as they ran; ``XLA Modules`` and
``Steps`` lines span idle time inside a program and are not used). The traced
window is the host's own: from the first to the last of the benchmark's
``bench.*`` annotations on the host plane, which puts spans and device events on
the profiler's one clock.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def busy_and_gaps(ops: Iterable[Interval], lo: float, hi: float) -> Tuple[float, List[Interval]]:
    """Seconds (in the intervals' unit) with an operation running inside
    [lo, hi], and the idle gaps between them."""
    merged = union(clip(ops, lo, hi))
    busy = sum(b - a for a, b in merged)
    gaps, at = [], lo
    for a, b in merged:
        if a > at:
            gaps.append((at, a))
        at = b
    if hi > at:
        gaps.append((at, hi))
    return busy, gaps


def label_gaps(gaps: Sequence[Interval], spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle time by the host span that covered most of each gap (``other`` where none did)."""
    out: Dict[str, float] = {}
    for a, b in gaps:
        cover: Dict[str, float] = {}
        for name, s0, s1 in spans:
            o = min(b, s1) - max(a, s0)
            if o > 0:
                cover[name] = cover.get(name, 0.0) + o
        covered = sum(cover.values())
        if (b - a) - covered > max(cover.values(), default=0.0):
            name = "other"
        else:
            name = max(cover, key=cover.get)
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def top(pairs: Dict[str, float], n: int = 10) -> List[List[Any]]:
    return [[k, v] for k, v in sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]


def summarize(
    device_ops: Dict[str, List[Tuple[str, float, float]]],
    host_spans: List[Tuple[str, float, float]],
) -> Dict[str, Any]:
    """``device_ops``: plane -> (op name, start s, end s); ``host_spans``: (name, start s, end s),
    all on one clock. The window is the extent of the host spans."""
    if not host_spans:
        raise ValueError("the trace holds none of the benchmark's spans: nothing bounds the window")
    lo = min(s for _, s, _ in host_spans)
    hi = max(e for _, _, e in host_spans)
    busy_each, gaps0, by_op = [], [], {}
    for i, (plane, ops) in enumerate(sorted(device_ops.items())):
        busy, gaps = busy_and_gaps([(a, b) for _, a, b in ops], lo, hi)
        busy_each.append(busy)
        if i == 0:
            gaps0 = gaps
            for name, a, b in ops:
                o = min(b, hi) - max(a, lo)
                if o > 0:
                    by_op[name] = by_op.get(name, 0.0) + o
    if not busy_each or max(busy_each) <= 0:
        raise ValueError("no operation ran on a device inside the traced window")
    return {
        "busy_s": sum(busy_each) / len(busy_each),
        "window_s": hi - lo,
        "n_devices": len(busy_each),
        "breakdown": {"device_ops": top(by_op), "idle_gaps": top(label_gaps(gaps0, host_spans))},
    }


def read_xplane(path: str):
    """(device plane -> op events, host ``bench.*`` spans), seconds on the trace's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    host_spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    # an event is named by its whole HLO line: keep the instruction's name
                    device_ops[plane.name] = [
                        (ev.name.split(" = ", 1)[0][:64], ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host_spans.append(
                            (ev.name[len(SPAN_PREFIX):], ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        )
    return device_ops, host_spans


def reduce_dir(trace_dir: str) -> Dict[str, Any]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    return summarize(*read_xplane(paths[0]))
