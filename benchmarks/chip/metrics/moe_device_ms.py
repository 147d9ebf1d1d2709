"""Device self time a step under the scopes of the expert layers (the configuration's count file lists
them under the layer ``expert layer``: routing, the sort and the gathers, the grouped products; forwards,
recomputed and backwards), from the driver's reduction of the capture by scope (scopes.py).

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).layer_ms(run, "expert layer")
