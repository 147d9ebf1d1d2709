"""The trace-and-lower part of ``setup_compile_s``: ``process_stats()["lower_seconds"]`` at the
start of the window, which ``GuardedFn.aot_compile`` counts apart from compile-or-load. No
persistent cache saves it.

Read in the ``--trace 1`` run; it does not depend on the length of the window.
"""


def read(run):
    return run["compile"]["at_window_start"].get("lower_seconds")
