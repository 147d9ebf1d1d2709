"""The compile requests the persistent compile cache answered, every program of the process, each from the request
to the loaded executable (the key, the read, the load): ``process_stats()["cache_retrieval_seconds"]`` at the start
of the window, JAX's ``backend_compile_duration`` of each request inside which its ``cache_retrieval_time_sec``
fired. Such a request is not in ``setup_xla_compile_s``.

Read in the ``--trace 1`` run; a program whose listener sums no such events (the parent of PR 39) has nothing to read.
"""


def read(run):
    return run["compile"]["at_window_start"].get("cache_retrieval_seconds")
