"""Retraces + AOT fallbacks + persistent-cache misses (entries compiled anew)
counted by ``core.compile`` between the window's start and its end; should read 0.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""


def read(run):
    a, b = run["compile"]["at_window_start"], run["compile"]["at_window_end"]
    return sum(b[k] - a[k] for k in ("retraces", "aot_fallbacks", "cache_misses"))
