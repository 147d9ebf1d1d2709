"""Mean host time inside prefetcher.get per train call of the window, from the benchmark's own ``sample`` span.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""


def read(run):
    rows = [b - a for name, a, b in run["spans"] if name == "sample"]
    return 1e3 * sum(rows) / len(rows) if rows else None
