"""Device self time a step under the scopes of the head and the loss (the configuration's count file
lists them under the layer ``head and loss``: the logits over the held vocabulary in chunks, log-softmax,
entropy, the critic and the PPO losses; forwards, recomputed and backwards), from the driver's reduction
of the capture by scope (scopes.py).

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).layer_ms(run, "head and loss")
