"""The experts' grouped products against the chip's roofline: the least time the chip could take for
them (the larger of the counted FLOPs of the pairs computed here over the bf16 peak, and the bytes
they must move over the memory's rate; the family ``gmm`` of the configuration's count file) over the
device self time a step under that family's whole scope, which also holds the pairs' gathers, the
activation and what is recomputed going backwards: they are in the time and not in the count.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).roofline_pct(run, "gmm", kernels_only=False)
