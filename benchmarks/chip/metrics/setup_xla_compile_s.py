"""XLA's compile of what the persistent cache did not hold, every program of the process:
``process_stats()["backend_compile_seconds"]`` at the start of the window. JAX times a whole compile request under
one event, a load from the cache included; the program's listener books a request the cache answered under
``setup_cache_load_s`` instead, so the two never count the same second. On a warm run this is the programs the
cache does not keep (compiled in under ``jax_persistent_cache_min_compile_time_secs``) and any it lost.

Read in the ``--trace 1`` run; a program whose listener sums no such events (the parent of PR 39) has nothing to read.
"""


def read(run):
    return run["compile"]["at_window_start"].get("backend_compile_seconds")
