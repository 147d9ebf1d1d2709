"""``core.compile.process_stats()["compile_seconds"]`` at the start of the window.

Read in the ``--trace 1`` run; it does not depend on the length of the window.
"""


def read(run):
    return run["compile"]["at_window_start"]["compile_seconds"]
