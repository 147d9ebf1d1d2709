"""The fullest held expert's rows over the mean of the held experts' rows, the largest over the expert
layers: the program's counter ``Moe/load_max_over_mean`` in the metrics of the window's last train call.

Read in the ``--trace 1`` run; a counter of one step, it does not depend on the length of the window.
"""


def read(run):
    return run.get("counters", {}).get("Moe/load_max_over_mean")
