"""Device self time a step under the scope of the routing alone (the configuration's count file lists it under the
layer ``routing``: the router's float32 product, the top-k, the softmax over the chosen, the sort of the pairs and
their counts; forwards, recomputed and backwards), from the driver's reduction of the capture by scope (scopes.py).
Part of ``moe_device_ms``. In a model whose router reads the block's input this work waits for nothing in the block:
it is what a schedule could hide behind the attention kernels, and what an exchange across chips could start from
early. Nothing where the count file lists no such layer.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).layer_ms(run, "routing")
