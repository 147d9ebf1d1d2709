"""From a profiler capture to device time by model part, and from the program's
own spans and counters to numbers.

    python3 benchmarks/chip/scopes.py <trace_dir> [--config <name>]

``dv3.train`` wraps its parts in ``jax.named_scope`` under the names
``flops.py`` counts them by (``SCOPES``), and while a capture runs the program
writes each of its spans into it as ``sheeprl.<name>`` (``telemetry/trace.py``).
This file reads both out of one ``.xplane.pb``:

- **Where the scope is.** XLA:TPU names a device event by its HLO instruction
  (``%fusion.19 = ...``) and gives it no framework path, neither in the name nor
  in its stats (looked at by hand, PR 27). The path is in the compiled program's
  text (``metadata={op_name="jit(train)/.../transpose(jvp(encoder))/..."}``). So
  whoever keeps a capture writes each program's ``Compiled.as_text()`` beside
  it as ``<trace_dir>/<anything>.hlo.txt``; an event belongs to the program whose
  run on the ``XLA Modules`` line covers it, and its scope is the innermost
  (last) of ``SCOPES`` on its instruction's path. Events with none are
  ``unscoped``; events of a program that left no text are ``other_programs``.
- **Self time.** A ``%while`` event covers the events of its body, so each
  instant goes to the innermost event that covers it (``segments``) and the
  scopes sum to the device's busy time.
- **Idle gaps** are the gaps of ``reduce.busy_and_gaps``, labelled once for each
  host thread by ``reduce.label_gaps`` with that thread's innermost
  ``sheeprl.*`` spans (the prefetch worker's spans last 80 ms and would cover
  every gap the train loop's thread left). The window is the extent of the spans.

Nothing in the harness calls the reduction yet (the driver deletes the trace
before a reader runs; the ``benchmark`` issue after PR 27 wires it in). The
metric readers of PR 27 use the last three functions, which need no trace.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import HERE, load_json, load_module, peak_for  # noqa: E402

reduce = load_module("", "reduce", HERE)

Event = Tuple[Any, float, float]  # (label, start s, end s)
SPAN_PREFIX = "sheeprl."
UNCOUNTED = ("world_opt", "actor_opt", "critic_opt", "moments", "target_ema", "player_ravel")
# the parts flops.py counts, then the work it does not count
SCOPES = (
    "encoder", "dynamic_scan", "decoder", "reward_head", "continue_head",
    "imagination_rollout", "imagination_actor", "imagination_heads", "critic_update", "target_critic",
) + UNCOUNTED
UNSCOPED, OTHER_PROGRAMS = "unscoped", "other_programs"

_WORD = re.compile(r"[A-Za-z_][A-Za-z_0-9]*")
_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?(%?[\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')


def scope_of(op_name: str, scopes: Sequence[str] = SCOPES) -> str:
    """The innermost of ``scopes`` on a framework-op path such as
    ``jit(train)/while/body/transpose(jvp(encoder))/conv_general_dilated``."""
    found = [w for w in _WORD.findall(op_name) if w in scopes]
    return found[-1] if found else UNSCOPED


def op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, instruction -> op_name) of one compiled program's text."""
    head = re.match(r"HloModule ([\w.\-]+)", hlo_text)
    table = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            table[m.group(1).lstrip("%")] = m.group(2)
    return (head.group(1) if head else ""), table


def segments(events: Iterable[Event]) -> List[Event]:
    """Disjoint (label, start, end): each instant goes to the innermost event
    covering it, the one that started last. Events nest or follow each other; one
    that outlasts the event it started in keeps its own time and shortens the outer's."""
    out: List[Event] = []
    stack: List[Tuple[str, float]] = []
    at = 0.0
    for label, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            inner, end = stack.pop()
            if end > at:
                out.append((inner, at, end))
                at = end
        if stack and a > at:
            out.append((stack[-1][0], at, a))
        at = a
        stack.append((label, b))
    while stack:
        inner, end = stack.pop()
        if end > at:
            out.append((inner, at, end))
            at = end
    return out


def self_times(events: Iterable[Event], lo: float = float("-inf"), hi: float = float("inf")) -> Dict[str, float]:
    """Seconds by label inside [lo, hi], by ``segments``: the labels sum to the events' union."""
    out: Dict[str, float] = {}
    for label, a, b in segments(events):
        d = min(b, hi) - max(a, lo)
        if d > 0:
            out[label] = out.get(label, 0.0) + d
    return out


def summarize(
    ops: List[Event],
    modules: List[Event],
    host_spans: Dict[str, List[Event]],
    tables: Dict[str, Dict[str, str]],
) -> Dict[str, Any]:
    """One device's ``XLA Ops`` events (instruction, start, end) and ``XLA Modules``
    runs (program, start, end), the host's ``sheeprl.*`` spans by thread, and the
    instruction -> op_name table of each program that left its text."""
    spans = [s for line in host_spans.values() for s in line]
    if not spans:
        raise ValueError("the capture holds no sheeprl.* span: nothing bounds the window")
    lo, hi = min(a for _, a, _ in spans), max(b for _, _, b in spans)
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]

    def scope(instruction: str, at: float) -> str:
        i = bisect.bisect_right(starts, at) - 1
        program = modules[i][0] if i >= 0 and at < modules[i][2] else ""
        table = tables.get(program)
        if table is None:
            return OTHER_PROGRAMS
        return scope_of(table.get(instruction, ""))

    # one sweep over (scope, instruction): the scopes' self times and, for the unscoped, whose they are
    by_both = self_times([((scope(name, a), name), a, b) for name, a, b in ops], lo, hi)
    by_scope: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    for (label, name), seconds in by_both.items():
        by_scope[label] = by_scope.get(label, 0.0) + seconds
        if label == UNSCOPED:
            unscoped[name] = seconds
    busy, gaps = reduce.busy_and_gaps([(a, b) for _, a, b in ops], lo, hi)
    # by thread, and within a thread by its innermost span: a gap is put down to what that thread
    # did in it, not to the call around it, nor to what another thread happened to do meanwhile
    long_gaps = [g for g in gaps if g[1] - g[0] > 1e-3]
    leaves = {thread: segments(line) for thread, line in host_spans.items()}
    # runs of the programs that left their text, a run cut by the window's edge counting by its part inside
    steps = sum(
        max(0.0, min(b, hi) - max(a, lo)) / (b - a) for name, a, b in modules if name in tables and b > a
    )
    return {
        "window_s": hi - lo,
        "busy_s": busy,
        "steps": steps,
        "scopes": by_scope,
        "unscoped_ops": reduce.top(unscoped),
        "idle_gaps": {thread: reduce.label_gaps(gaps, rows) for thread, rows in leaves.items()},
        "idle_gaps_over_1ms": {thread: reduce.label_gaps(long_gaps, rows) for thread, rows in leaves.items()},
    }


def read_capture(trace_dir: str):
    """(device plane -> (ops, module runs, the plane's ``device_type_string``), host thread ->
    sheeprl.* spans, program -> table), seconds on the capture's clock."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")

    def events(line, name_of) -> List[Event]:
        return [(name_of(ev.name), ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]

    devices: Dict[str, Tuple[List[Event], List[Event], str]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if reduce.OPS_LINE in lines:
                devices[plane.name] = (
                    events(lines[reduce.OPS_LINE], lambda n: n.split(" = ", 1)[0].lstrip("%")),
                    events(lines["XLA Modules"], lambda n: n.split("(", 1)[0]) if "XLA Modules" in lines else [],
                    str(dict(plane.stats).get("device_type_string", "")),
                )
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):  # a line is a thread; Python's threads are all named "python"
                rows = [e for e in events(line, str) if e[0].startswith(SPAN_PREFIX)]
                if rows:
                    host[f"{line.name}#{i}"] = [(n[len(SPAN_PREFIX):], a, b) for n, a, b in rows]
    tables = {}
    for path in glob.glob(os.path.join(trace_dir, "*.hlo.txt")):
        with open(path) as f:
            program, table = op_names(f.read())
        tables[program] = table
    return devices, host, tables


def report(summary: Dict[str, Any], config: Optional[Dict[str, Any]] = None, peak: Optional[Dict[str, Any]] = None) -> str:
    """The table: per scope device ms a step, share of busy time and, with a
    configuration, the part's model FLOPs over that time over the chip's peak."""
    busy, steps = summary["busy_s"], summary["steps"]
    parts = load_module("", "flops", HERE).COUNTS[config["flops"]](config["sizes"]) if config else {}
    total = sum(summary["scopes"].values())
    lines = [
        f"window {summary['window_s']:.4f} s, busy {busy:.4f} s ({100 * busy / summary['window_s']:.2f}%), "
        f"{steps:.2f} runs of the scoped programs; scopes sum to {total:.4f} s ({100 * total / busy:.2f}% of busy)",
        f"{'scope':<22}{'device s':>10}{'ms/step':>10}{'% busy':>8}{'TFLOP/step':>12}{'% of peak':>10}",
    ]
    for name, seconds in sorted(summary["scopes"].items(), key=lambda kv: -kv[1]):
        row = f"{name:<22}{seconds:>10.4f}{1e3 * seconds / max(steps, 1e-9):>10.3f}{100 * seconds / busy:>8.2f}"
        if name in parts and peak and seconds > 0:
            share = 100 * parts[name] * steps / seconds / peak["bf16_flops_per_s"]
            row += f"{parts[name] / 1e12:>12.4f}{share:>10.2f}"
        lines.append(row)
    lines.append("unscoped, by instruction: " + ", ".join(f"{n} {s:.4f}" for n, s in summary["unscoped_ops"]))
    for key in ("idle_gaps", "idle_gaps_over_1ms"):
        for thread, gaps in sorted(summary[key].items()):
            by_span = ", ".join(f"{n} {s:.4f}" for n, s in sorted(gaps.items(), key=lambda kv: -kv[1]))
            lines.append(f"{key} (s) by sheeprl.* span of host thread {thread}: {by_span}")
    return "\n".join(lines)


# ---- the program's ring and counters, for the metric readers (no trace needed)


def ring_spans(name: str) -> List[Tuple[float, Dict[str, Any]]]:
    """(seconds, args) of every completed span ``name`` in the program's ring. The
    benchmark configures no tracer, so the ring holds the spans of the traced window
    and nothing else; a program without such spans (the parent of PR 27) gives []."""
    from sheeprl_tpu.telemetry import trace

    tracer = trace.get_tracer()
    if tracer is None:
        return []
    # a ring row is (name, plane, ph, ts_us, dur_us, tid, span_id, parent_id, args)
    return [(ev[4] * 1e-6, ev[8] or {}) for ev in tracer.events() if ev[0] == name and ev[2] == "X"]


def span_ms_per_step(run: Dict[str, Any], *names: str) -> Optional[float]:
    """Host milliseconds inside the spans ``names`` per step completed in the window."""
    rows = [seconds for name in names for seconds, _ in ring_spans(name)]
    steps = run["steps"]["in_window"]
    return 1e3 * sum(rows) / steps if rows and steps else None


def train_call_ms(run: Dict[str, Any], counter: str) -> Optional[float]:
    """``counter`` (``route_seconds`` / ``execute_seconds``) of the guarded function
    that the window called once a step, per call, from the driver's two snapshots of
    ``process_stats()``. None where the program keeps no such counter."""
    before, after = (run["compile"][k]["functions"] for k in ("at_window_start", "at_window_end"))
    hits = []
    for name, now in after.items():
        if counter in now:
            was = before.get(name, {})
            calls = now["calls"] - was.get("calls", 0)
            if calls == run["attempted"] and calls > 0:
                hits.append((now[counter] - was.get(counter, 0.0)) / calls)
    return 1e3 * max(hits) if hits else None


def main(argv: List[str]) -> int:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("trace_dir")
    parser.add_argument("--config", help="a configuration of configs/: adds each part's FLOPs share of the peak")
    args = parser.parse_args(argv)
    devices, host, tables = read_capture(args.trace_dir)
    config = load_json(HERE, "configs", f"{args.config}.json") if args.config else None
    # the capture says "TPU v5 Lite" where jax's device_kind, the table's key, says "TPU v5 lite"
    kinds = {kind.lower(): kind for kind in load_json(HERE, "peaks.json")}
    for plane, (ops, modules, device_type) in sorted(devices.items()):
        peak = peak_for(kinds.get(device_type.lower(), device_type), HERE) if config else None
        print(plane, device_type)
        print(report(summarize(ops, modules, host, tables), config, peak))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
