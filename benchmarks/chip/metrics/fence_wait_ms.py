"""Mean host time blocked on the step's parameters per train call (the fence ``main()`` makes
while its timer is on), from the benchmark's own ``fence`` span: device time the host did not hide.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""


def read(run):
    rows = [b - a for name, a, b in run["spans"] if name == "fence"]
    return 1e3 * sum(rows) / len(rows) if rows else None
