"""JAX's tracing and lowering to MLIR, every program of the process (the benchmark's reference and probes too):
``process_stats()["trace_seconds"]`` at the start of the window, summed from JAX's own duration events, a trace
opened inside another counted once. No persistent cache saves it.

Read in the ``--trace 1`` run; a program whose listener sums no such events (the parent of PR 39) has nothing to read.
"""


def read(run):
    return run["compile"]["at_window_start"].get("trace_seconds")
