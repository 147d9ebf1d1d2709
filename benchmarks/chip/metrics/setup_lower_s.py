"""The trace-and-lower part of ``setup_compile_s``: ``process_stats()["lower_seconds"]`` at the
start of the window. ``GuardedFn.aot_compile`` counts it apart from compile-or-load; a call
through plain ``jit`` that traced (the language-model cells' train function) counts the trace
and lowering seconds that JAX's own events timed on the calling thread during that call, so the
reading is never more than ``setup_compile_s``. No persistent cache saves it.

Read in the ``--trace 1`` run; it does not depend on the length of the window.
"""


def read(run):
    return run["compile"]["at_window_start"].get("lower_seconds")
