"""The program's imports: the union of the set-up phases ``import`` (``sheeprl_tpu/__init__.py`` from its first
statement to its last, ``import jax`` included) and ``import.<package>`` (an algorithm's package, the modules it
pulls in: flax, optax, gymnasium, the model), from ``process_stats()["setup_phases"]`` at the start of the window.
The TPU's start is not in it: the first ``jax.devices()`` is the harness's.

Read in the ``--trace 1`` run; a program that keeps no set-up record (the parent of PR 39) has nothing to read.
"""
from common import load_module


def read(run):
    phases = run["compile"]["at_window_start"].get("setup_phases")
    if phases is None:
        return None
    spans = ((a, b) for name, a, b in phases if name == "import" or name.startswith("import."))
    return sum(b - a for a, b in load_module("", "reduce", run["cell"]["here"]).union(spans))
