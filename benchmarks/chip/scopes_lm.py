"""Device self time by scope for programs whose parts run under dotted scope
names (``lm.moe.experts``, ``ppo.opt``): what ``scopes.py`` does for ``dv3.train``,
whose one-word names its ``scope_of`` is written for. Everything else (the
capture's reading, the self-time sweep, the instruction -> op_name table) is
``scopes.py``'s own.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional

from common import HERE, load_module

scopes = load_module("", "scopes", HERE)

# the parts flops_lfm2.py counts, then the work it does not count
COUNTED = ("lm.embed", "lm.conv", "lm.attn", "lm.dense_ffn", "lm.moe.route", "lm.moe.experts", "lm.head")
SCOPES = COUNTED + ("ppo.loss", "ppo.opt")
_SCOPE = re.compile(r"\b(?:lm|ppo)(?:\.[a-z_]+)+")


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` on a framework-op path such as
    ``jit(train)/while/body/ppo.loss/transpose(jvp(lm.moe.experts))/ragged_dot``."""
    found = [w for w in _SCOPE.findall(op_name) if w in SCOPES]
    return found[-1] if found else scopes.UNSCOPED


_KERNEL = re.compile(r'^\s*(?:ROOT )?(%?[\w.\-]+) = .*custom_call_target="tpu_custom_call"')


def kernel_instructions(hlo_text: str) -> set:
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


def summarize(ops, modules, host_spans, tables, kernels: Optional[Dict[str, set]] = None) -> Dict[str, Any]:
    """As ``scopes.summarize``, with this file's ``scope_of``: seconds by scope inside the
    extent of the ``sheeprl.*`` spans, the device's busy time and the runs of the scoped programs;
    ``kernels`` (program -> its Pallas kernels' instructions) adds the kernels' seconds by scope."""
    spans = [s for line in host_spans.values() for s in line]
    if not spans:
        raise ValueError("the capture holds no sheeprl.* span: nothing bounds the window")
    lo, hi = min(a for _, a, _ in spans), max(b for _, _, b in spans)
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]

    def scope(instruction: str, at: float) -> str:
        i = bisect.bisect_right(starts, at) - 1
        table = tables.get(modules[i][0] if i >= 0 and at < modules[i][2] else "")
        return scopes.OTHER_PROGRAMS if table is None else scope_of(table.get(instruction, ""))

    by_both = scopes.self_times([((scope(name, a), name), a, b) for name, a, b in ops], lo, hi)
    by_scope: Dict[str, float] = {}
    by_op: Dict[str, Dict[str, float]] = {}
    by_kernel: Dict[str, float] = {}
    kernel_names = set().union(*kernels.values()) if kernels else set()
    for (label, name), seconds in by_both.items():
        by_scope[label] = by_scope.get(label, 0.0) + seconds
        by_op.setdefault(label, {})[name] = seconds
        if name in kernel_names:
            by_kernel[label] = by_kernel.get(label, 0.0) + seconds
    steps = sum(max(0.0, min(b, hi) - max(a, lo)) / (b - a) for name, a, b in modules if name in tables and b > a)
    return {
        "window_s": hi - lo,
        "busy_s": sum(by_scope.values()),
        "steps": steps,
        "scopes": by_scope,
        "kernels": by_kernel,
        "top_ops": {label: scopes.reduce.top(ops_, 4) for label, ops_ in by_op.items()},
        # what no scope covers, with the path its instruction does have: where to put the next scope
        "unscoped_ops": [
            [name, seconds, next((t[name] for t in tables.values() if name in t), "")[-96:]]
            for name, seconds in scopes.reduce.top(by_op.get(scopes.UNSCOPED, {}), 12)
        ],
    }


def reduce_dir(trace_dir: str) -> Optional[Dict[str, Any]]:
    """The first device's summary, or None where the capture has no device plane or no program text."""
    devices, host, tables = scopes.read_capture(trace_dir)
    if not devices or not tables or not host:
        return None
    ops, modules, _kind = devices[sorted(devices)[0]]
    kernels = {}
    for path in glob.glob(os.path.join(trace_dir, "*.hlo.txt")):
        with open(path) as f:
            text = f.read()
        kernels[scopes.op_names(text)[0]] = kernel_instructions(text)
    return summarize(ops, modules, host, tables, kernels)


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
