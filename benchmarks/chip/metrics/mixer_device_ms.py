"""Device self time a step under the scopes ``lm.conv`` and ``lm.attn`` (the gated short
convolutions and the attention layer; forwards, recomputed and backwards), from the driver's
reduction of the capture by scope (scopes_lm.py).

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes_lm", run["cell"]["here"]).scope_ms(run, "lm.conv", "lm.attn")
