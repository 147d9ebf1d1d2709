"""MiB handed to ``device_put`` by the prefetcher per step: the ``bytes`` the program counts on each
``prefetch.h2d`` span, summed over the window. A transfer carries four batches, so a 23-step window
holds five or six of them and the number moves by a transfer's share from run to run.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    rows = load_module("", "scopes", run["cell"]["here"]).ring_spans("prefetch.h2d")
    steps = run["steps"]["in_window"]
    return sum(args.get("bytes", 0) for _, args in rows) / steps / 2**20 if rows and steps else None
