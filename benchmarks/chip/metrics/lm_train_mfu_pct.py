"""The whole step's share of the chip's bf16 peak for a token-policy cell: the model FLOPs
of one gradient step (the configuration's count file, found by ``flops.py``; the experts' part from the
pairs the program counted on this chip in the window's last step, ``Moe/pairs_here``) x steps completed in
the window / window / (chips x peak from peaks.json). It bounds every later claim in the cell. Read from a
CPU rehearsal it is nothing.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    if run.get("peak") is None or not run["steps"]["in_window"]:
        return None
    pairs = run.get("counters", {}).get("Moe/pairs_here")
    flops = load_module("", "flops", run["cell"]["here"]).step_flops(run["config"], pairs)
    rate = flops * run["steps"]["in_window"] / run["window_s"]
    return 100.0 * rate / (run["n_devices"] * run["peak"]["bf16_flops_per_s"])
