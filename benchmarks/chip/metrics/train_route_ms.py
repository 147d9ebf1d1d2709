"""Host time ``GuardedFn.__call__`` spends finding the executable per train call: the selector's
pick (no leaf read with one executable registered; with several, a flatten and the few leaves that
tell them apart), and on a miss also the refused dispatch and the full route (``abstract_signature``
over every leaf of parameters and optimizer states, the lookup, a wait for a pending warm-up). It is
the program's ``route_seconds`` counter of the function the window called once a step, between the
driver's two snapshots of ``process_stats()``, and counts hits and misses alike (``route_hits`` /
``route_misses`` tell them apart; no metric reads them). With ``train_execute_ms`` it splits
``dispatch_ms``.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds`` (4 s,
some 36 steps of ``dv3_xl.chip_player``), whatever ``--seconds`` asks for.
"""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).train_call_ms(run, "route_seconds")
