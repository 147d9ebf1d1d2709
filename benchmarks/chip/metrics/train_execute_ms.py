"""Host time inside the compiled executable's own call per train call (argument checks, donation,
the enqueue): the program's ``execute_seconds`` counter of the function the window called once a
step, between the driver's two snapshots of ``process_stats()``.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).train_call_ms(run, "execute_seconds")
