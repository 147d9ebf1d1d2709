"""Time ``sample_fn`` (the replay buffer's sampling and native gather) takes under the IO lock per
step, from the program's ``prefetch.sample`` spans: on the prefetch worker's thread in the steady
state, four batches a span, so it is off the train loop's path unless ``prefetch_wait_ms`` rises.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 23 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).span_ms_per_step(run, "prefetch.sample")
