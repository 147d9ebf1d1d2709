"""Time the prefetch worker spends moving a sampled chunk to the device per step: the
``device_put`` calls (``prefetch.h2d``) and the fence that waits for them (``prefetch.h2d_fence``),
from the program's spans.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).span_ms_per_step(run, "prefetch.h2d", "prefetch.h2d_fence")
