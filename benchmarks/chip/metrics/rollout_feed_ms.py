"""Host time a step inside ``ppo_recurrent.rollout_feed`` (GAE, the split of a rollout into padded
sequences and their transfer to the device), from the program's own ``rollout.feed`` spans (the ring
of the capture, ``telemetry/trace.py``): what ``sample_wait_ms`` reads from outside, without the copy
of the pool's rollout that the benchmark makes around it.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).span_ms_per_step(run, "rollout.feed")
