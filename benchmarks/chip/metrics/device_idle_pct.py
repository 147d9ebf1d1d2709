"""1 - union of the device-op intervals / traced window, from the .xplane.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""


def read(run):
    if "trace" not in run:
        return None
    return 100.0 * (1.0 - run["trace"]["busy_s"] / run["trace"]["window_s"])
