"""The whole step's share of the chip's bf16 peak: the yardstick's model FLOPs
of one gradient step (flops.py) x steps completed in the window / window /
(chips x peak from peaks.json). Read from a CPU rehearsal it is nothing.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    if run.get("peak") is None or not run["steps"]["in_window"]:
        return None
    flops = load_module("", "flops", run["cell"]["here"]).step_flops(run["config"])
    rate = flops * run["steps"]["in_window"] / run["window_s"]
    return 100.0 * rate / (run["n_devices"] * run["peak"]["bf16_flops_per_s"])
