"""Device self time a step under the scopes of the token mixers (the configuration's count file lists
them under the layer ``token mixers``: gated short convolutions and attention; forwards, recomputed and
backwards), from the driver's reduction of the capture by scope (scopes.py).

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).layer_ms(run, "token mixers")
