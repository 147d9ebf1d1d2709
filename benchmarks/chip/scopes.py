"""From a profiler capture to device time by model part, and from the program's
own spans and counters to numbers.

    python3 benchmarks/chip/scopes.py <trace_dir> [--config <name>]

A train program wraps its parts in ``jax.named_scope`` under the names its
configuration's count file counts them by (``flops.scopes_of``: one word as in
``dv3.train``, or dotted as ``lm.moe.experts``), and while a capture runs the
program writes each of its spans into it as ``sheeprl.<name>``
(``telemetry/trace.py``). This file reads both out of one ``.xplane.pb``:

- **Where the scope is.** XLA:TPU names a device event by its HLO instruction
  (``%fusion.19 = ...``) and gives it no framework path, neither in the name nor
  in its stats (looked at by hand, PR 27). The path is in the compiled program's
  text (``metadata={op_name="jit(train)/.../transpose(jvp(encoder))/..."}``). So
  whoever keeps a capture writes each program's ``Compiled.as_text()`` beside
  it as ``<trace_dir>/<anything>.hlo.txt``; an event belongs to the program whose
  run on the ``XLA Modules`` line covers it, and its scope is the innermost
  (last) of the configuration's scopes on its instruction's path. Events with
  none are ``unscoped``; events of a program that left no text are
  ``other_programs``. The instructions that are Pallas kernels
  (``tpu_custom_call``) are summed by scope a second time, apart.
- **Self time.** A ``%while`` event covers the events of its body, so each
  instant goes to the innermost event that covers it (``segments``) and the
  scopes sum to the device's busy time.
- **Idle gaps** are the gaps of ``reduce.busy_and_gaps``, labelled once for each
  host thread by ``reduce.label_gaps`` with that thread's innermost
  ``sheeprl.*`` spans (the prefetch worker's spans last 80 ms and would cover
  every gap the train loop's thread left). The window is the extent of the spans.

``drivers/seq_learner.py`` writes its train program's text beside the capture
(`compiled_text`) and keeps `reduce_dir`'s summary as ``run["scopes"]`` before it
deletes the trace; `scope_ms`, `kernel_ms`, `layer_ms` and `roofline_pct` are what
the metric readers take from it. ``drivers/learner.py`` does not yet. The last
three functions read the program's ring and counters and need no trace.
"""

from __future__ import annotations

import argparse
import bisect
import functools
import glob
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from common import load_json, load_module, peak_for  # noqa: E402

reduce = load_module("", "reduce", HERE)
flops = load_module("", "flops", HERE)

Event = Tuple[Any, float, float]  # (label, start s, end s)
SPAN_PREFIX = "sheeprl."
UNSCOPED, OTHER_PROGRAMS = "unscoped", "other_programs"

_NAME = re.compile(r"[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)*")  # a word, or words joined by dots: a dotted scope reads whole
_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?(%?[\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')
_KERNEL = re.compile(r'^\s*(?:ROOT )?(%?[\w.\-]+) = .*custom_call_target="tpu_custom_call"')


@functools.lru_cache(maxsize=None)
def known_scopes() -> Tuple[str, ...]:
    """The scopes of every configuration under ``configs/`` together, each by its count file: what is
    looked for where no configuration is named."""
    found: List[str] = []
    for path in sorted(glob.glob(os.path.join(HERE, "configs", "*.json"))):
        found += [name for name in flops.scopes_of(load_json(path)) if name not in found]
    return tuple(found)


def scope_of(op_name: str, scopes: Optional[Sequence[str]] = None) -> str:
    """The innermost of ``scopes`` (a configuration's ``flops.scopes_of``; every configuration's where
    none is given) on a framework-op path such as
    ``jit(train)/while/body/transpose(jvp(encoder))/conv_general_dilated`` or
    ``jit(train)/while/body/ppo.loss/transpose(jvp(lm.moe.experts))/ragged_dot``."""
    scopes = known_scopes() if scopes is None else scopes
    found = [w for w in _NAME.findall(op_name) if w in scopes]
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


def kernel_instructions(hlo_text: str) -> Set[str]:
    """The instructions of one compiled program's text that are Pallas (Mosaic) kernels."""
    return {m.group(1).lstrip("%") for m in map(_KERNEL.match, hlo_text.splitlines()) if m}


def compiled_text(guarded_fn, specs) -> str:
    """The compiled text of the program a ``GuardedFn`` runs for ``specs``: from its AOT
    registry where it has one; else its function lowered and compiled again under the name its
    plain-jit path runs it by (``guarded[<name>]``: the capture's ``XLA Modules`` line names a run by
    its program, and that is how text and events meet). The persistent cache has the executable."""
    import jax

    exes = guarded_fn.aot_executables()
    if exes:
        return exes[0].as_text()

    def same_name(*args, **kwargs):
        return guarded_fn.fun(*args, **kwargs)

    same_name.__name__ = f"guarded[{guarded_fn.name}]"
    same_name.__wrapped__ = guarded_fn.fun  # jit resolves static and donated arguments by the signature
    return jax.jit(same_name, **guarded_fn._jit_kwargs).lower(*specs).compile().as_text()


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
    scopes: Optional[Sequence[str]] = None,
    kernels: Optional[Dict[str, Set[str]]] = None,
    idle_by_thread: bool = True,
) -> Dict[str, Any]:
    """One device's ``XLA Ops`` events (instruction, start, end) and ``XLA Modules``
    runs (program, start, end), the host's ``sheeprl.*`` spans by thread, and the
    instruction -> op_name table of each program that left its text. Seconds by scope
    (``scopes``: see `scope_of`) inside the extent of the spans, the device's busy time
    and the runs of the scoped programs; ``kernels`` (program -> its Pallas kernels'
    instructions) adds the kernels' seconds by scope. ``idle_by_thread`` labels every idle
    gap once for each host thread, which a driver inside a run's 360 s leaves out."""
    spans = [s for line in host_spans.values() for s in line]
    if not spans:
        raise ValueError("the capture holds no sheeprl.* span: nothing bounds the window")
    lo, hi = min(a for _, a, _ in spans), max(b for _, _, b in spans)
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]

    found: Dict[str, str] = {}  # path -> scope: a step's instructions come again with every step

    def scope(instruction: str, at: float) -> str:
        i = bisect.bisect_right(starts, at) - 1
        table = tables.get(modules[i][0] if i >= 0 and at < modules[i][2] else "")
        if table is None:
            return OTHER_PROGRAMS
        path = table.get(instruction, "")
        if path not in found:
            found[path] = scope_of(path, scopes)
        return found[path]

    # one sweep over (scope, instruction): the scopes' self times and, within a scope, whose they are
    by_both = self_times([((scope(name, a), name), a, b) for name, a, b in ops], lo, hi)
    by_scope: Dict[str, float] = {}
    by_op: Dict[str, Dict[str, float]] = {}
    by_kernel: Dict[str, float] = {}
    kernel_names = set().union(*kernels.values()) if kernels else set()
    for (label, name), seconds in by_both.items():
        by_scope[label] = by_scope.get(label, 0.0) + seconds
        by_op.setdefault(label, {})[name] = seconds
        if name in kernel_names:
            by_kernel[label] = by_kernel.get(label, 0.0) + seconds
    # runs of the programs that left their text, a run cut by the window's edge counting by its part inside
    steps = sum(
        max(0.0, min(b, hi) - max(a, lo)) / (b - a) for name, a, b in modules if name in tables and b > a
    )
    out = {
        "window_s": hi - lo,
        "busy_s": sum(by_scope.values()),  # the self times sum to the union of the events
        "steps": steps,
        "scopes": by_scope,
        "kernels": by_kernel,
        "top_ops": {label: reduce.top(ops_, 4) for label, ops_ in by_op.items()},
        # what no scope covers, with the path its instruction does have: where to put the next scope
        "unscoped_ops": [
            [name, seconds, next((t[name] for t in tables.values() if name in t), "")[-96:]]
            for name, seconds in reduce.top(by_op.get(UNSCOPED, {}), 12)
        ],
    }
    if idle_by_thread:
        # by thread, and within a thread by its innermost span: a gap is put down to what that thread
        # did in it, not to the call around it, nor to what another thread happened to do meanwhile
        _busy, gaps = reduce.busy_and_gaps([(a, b) for _, a, b in ops], lo, hi)
        long_gaps = [g for g in gaps if g[1] - g[0] > 1e-3]
        leaves = {thread: segments(line) for thread, line in host_spans.items()}
        out["idle_gaps"] = {thread: reduce.label_gaps(gaps, rows) for thread, rows in leaves.items()}
        out["idle_gaps_over_1ms"] = {thread: reduce.label_gaps(long_gaps, rows) for thread, rows in leaves.items()}
    return out


def read_capture(trace_dir: str):
    """(device plane -> (ops, module runs, the plane's ``device_type_string``), host thread ->
    sheeprl.* spans, program -> table, program -> its Pallas kernels), seconds on the capture's clock."""
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
    tables, kernels = {}, {}
    for path in glob.glob(os.path.join(trace_dir, "*.hlo.txt")):
        with open(path) as f:
            text = f.read()
        program, tables[program] = op_names(text)
        kernels[program] = kernel_instructions(text)
    return devices, host, tables, kernels


def reduce_dir(trace_dir: str, scopes: Optional[Sequence[str]] = None) -> Optional[Dict[str, Any]]:
    """The first device's summary by ``scopes``, or None where the capture has no device plane or no
    program text. For a driver: the idle gaps by thread are left out."""
    devices, host, tables, kernels = read_capture(trace_dir)
    if not devices or not tables or not host:
        return None
    ops, modules, _kind = devices[sorted(devices)[0]]
    return summarize(ops, modules, host, tables, scopes, kernels, idle_by_thread=False)


def report(summary: Dict[str, Any], config: Optional[Dict[str, Any]] = None, peak: Optional[Dict[str, Any]] = None) -> str:
    """The table: per scope device ms a step, share of busy time and, with a
    configuration, the part's model FLOPs over that time over the chip's peak."""
    busy, steps = summary["busy_s"], summary["steps"]
    parts = flops.step_parts(config) if config else {}
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
    lines.append("unscoped, by instruction: " + ", ".join(f"{n} {s:.4f}" for n, s, _path in summary["unscoped_ops"]))
    for key in ("idle_gaps", "idle_gaps_over_1ms"):
        for thread, gaps in sorted(summary.get(key, {}).items()):
            by_span = ", ".join(f"{n} {s:.4f}" for n, s in sorted(gaps.items(), key=lambda kv: -kv[1]))
            lines.append(f"{key} (s) by sheeprl.* span of host thread {thread}: {by_span}")
    return "\n".join(lines)


# ---- a driver's reduction (``run["scopes"]``), for the metric readers


def ms_a_step(summary: Optional[Dict[str, Any]]) -> List[List[Any]]:
    """[scope, device ms a step] by time, then the largest unscoped instructions as [instruction, ms a step, its path]."""
    if not summary or not summary["steps"]:
        return []
    rows = [[k, 1e3 * v / summary["steps"]] for k, v in sorted(summary["scopes"].items(), key=lambda kv: -kv[1])]
    return rows + [[name, 1e3 * seconds / summary["steps"], path] for name, seconds, path in summary.get("unscoped_ops", [])]


def scope_ms(run: Dict[str, Any], *names: str) -> Optional[float]:
    """Device self time a step of the scopes ``names`` together, from the driver's reduction."""
    summary = run.get("scopes")
    if not summary or not summary["steps"]:
        return None
    seconds = [summary["scopes"][n] for n in names if n in summary["scopes"]]
    return 1e3 * sum(seconds) / summary["steps"] if seconds else None


def kernel_ms(run: Dict[str, Any], scope: str) -> Optional[float]:
    """Device self time a step of the Pallas kernels that run under ``scope``."""
    summary = run.get("scopes")
    if not summary or not summary["steps"] or scope not in summary.get("kernels", {}):
        return None
    return 1e3 * summary["kernels"][scope] / summary["steps"]


def layer_ms(run: Dict[str, Any], layer: str) -> Optional[float]:
    """Device self time a step of the scopes that the configuration's count file lists for ``layer``."""
    return scope_ms(run, *flops.layer_scopes(run["config"], layer))


def roofline_pct(run: Dict[str, Any], family: str, kernels_only: bool = True) -> Optional[float]:
    """The Pallas kernels of ``family`` against the chip's roofline: the least time the chip could take for what
    the configuration's count file says they must do a step (the larger of the FLOPs over the bf16 peak and
    the bytes over the memory's rate; the experts' part from the program's ``Moe/pairs_here``) over the device
    self time a step of the kernels under their scope, or of the whole scope. None where the configuration has
    no such kernels, the run no reduction or no peak: never 0."""
    if run.get("peak") is None:
        return None
    least = flops.kernel_least(run["config"], family, run.get("counters", {}).get("Moe/pairs_here"))
    if least is None:
        return None
    ms = kernel_ms(run, least["scope"]) if kernels_only else scope_ms(run, least["scope"])
    if not ms:
        return None
    least_s = max(least["flops"] / run["peak"]["bf16_flops_per_s"], least["bytes"] / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)


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
    parser.add_argument("--config", help="a configuration of configs/: its scopes alone, and each part's FLOPs share of the peak")
    args = parser.parse_args(argv)
    devices, host, tables, kernels = read_capture(args.trace_dir)
    config = load_json(HERE, "configs", f"{args.config}.json") if args.config else None
    names = flops.scopes_of(config) if config else None
    # the capture says "TPU v5 Lite" where jax's device_kind, the table's key, says "TPU v5 lite"
    kinds = {kind.lower(): kind for kind in load_json(HERE, "peaks.json")}
    for plane, (ops, modules, device_type) in sorted(devices.items()):
        peak = peak_for(kinds.get(device_type.lower(), device_type), HERE) if config else None
        print(plane, device_type)
        print(report(summarize(ops, modules, host, tables, names, kernels), config, peak))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
