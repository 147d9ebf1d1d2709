"""``memory_stats()["peak_bytes_in_use"]`` after the window, fullest device.

Read in the ``--trace 1`` run, after its window of ``trace_seconds`` (4 s); the peak is reached in the first step.
"""


def read(run):
    return run["memory_peak_bytes"] / 2**30 if run["memory_peak_bytes"] else None
