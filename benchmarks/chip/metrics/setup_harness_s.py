"""Set-up outside the program's phases: ``setup_s`` less the length of the union of every interval in
``process_stats()["setup_phases"]`` at the start of the window (the program's imports, construction, replay
buffer, compiles, the wait for the AOT warmup). It is not the benchmark's share alone: besides the harness's work
(the reference's weights, placement by load and scoring, the replay's rows, the ``Probe``, the first
``jax.devices()``, which starts the TPU) it holds the program's calls that keep no phase (the player's sync, the
steps that are compared, the modules a driver imports beyond an algorithm's package). A drift here is placed by
the harness's ``stage()`` lines, not by this number alone.

Read in the ``--trace 1`` run of a process that ran this cell alone; a program that keeps no set-up record (the
parent of PR 39) has nothing to read.
"""
from common import load_module


def read(run):
    phases = run["compile"]["at_window_start"].get("setup_phases")
    if phases is None:
        return None
    union = load_module("", "reduce", run["cell"]["here"]).union((a, b) for _, a, b in phases)
    return run["end_to_end"]["setup_s"] - sum(b - a for a, b in union)
