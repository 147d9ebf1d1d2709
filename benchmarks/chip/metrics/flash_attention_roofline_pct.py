"""The Pallas attention kernels (stock flash attention: forward, dq, dkv) against the chip's roofline:
the least time the chip could take for what they must do a step (the larger of the counted FLOPs over
the bf16 peak and the bytes over the memory's rate; the family ``attention`` of the configuration's count
file) over the device self time a step of the Pallas kernels under that family's scope (the
``tpu_custom_call`` instructions of the train program's text). With heads of 64 the products fill half of
the matrix unit's depth, so about half is the most this can read there. Nothing where the attention is
not a kernel's.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).roofline_pct(run, "attention")
