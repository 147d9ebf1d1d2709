"""The program's construction: the union of its set-up phases ``compose``, ``runtime`` (``build_runtime``),
``build_agent`` (its weights made and placed) and ``make_train_fn``, from ``process_stats()["setup_phases"]``
at the start of the window. Compiles are not in it: ``setup_compile_s`` reads them.

Read in the ``--trace 1`` run; a program that keeps no set-up record (the parent of PR 39) has nothing to read.
"""
from common import load_module

PHASES = ("compose", "runtime", "build_agent", "make_train_fn")


def read(run):
    phases = run["compile"]["at_window_start"].get("setup_phases")
    if phases is None:
        return None
    union = load_module("", "reduce", run["cell"]["here"]).union((a, b) for name, a, b in phases if name in PHASES)
    return sum(b - a for a, b in union)
