"""The share of the expert layers whose held pairs fitted the compact row buffer in the window's last
train call: the program's counter ``Moe/compact_share`` (1.0: every layer took the buffer of the width
of the pairs held here; under 1.0 the full-width fallback ran, exact and slower).

Read in the ``--trace 1`` run; a counter of one step, it does not depend on the length of the window.
"""


def read(run):
    return run.get("counters", {}).get("Moe/compact_share")
