"""The stock Pallas grouped-matmul kernels of the expert layers (megablox ``gmm`` forwards and for the
rows' cotangent, ``tgmm`` for the kernels' cotangent) against the chip's roofline: the least time the
chip could take for the products of the pairs computed here (the larger of their counted FLOPs over
the bf16 peak and their bytes over the memory's rate; the family ``gmm`` of the configuration's count
file) over the device self time a step of the Pallas kernels under that family's scope (the
``tpu_custom_call`` instructions of the train program's text). Where the forward products run twice (an
expert layer computed again going backwards), that is in the time and not in the count.
``moe_experts_roofline_pct`` reads the same count against the whole scope, gathers and activation included.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).roofline_pct(run, "gmm")
