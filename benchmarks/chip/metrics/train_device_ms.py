"""Device busy time in the traced window over the steps completed in it.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""


def read(run):
    steps = run["steps"]["in_window"]
    if "trace" not in run or not steps:
        return None
    return 1e3 * run["trace"]["busy_s"] / steps
