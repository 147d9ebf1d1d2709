"""Host time the train loop spends inside ``DevicePrefetcher.get`` per step, from the program's
own ``prefetch.get`` spans (the ring of the capture, ``telemetry/trace.py``): what
``sample_wait_ms`` reads from outside, without the benchmark's span around it.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).span_ms_per_step(run, "prefetch.get")
