"""The experts' grouped products against the chip's roofline: the least time the chip could take for
them (the larger of the counted FLOPs of the pairs computed here over the bf16 peak, and the bytes
they must move over the memory's rate; flops_lfm2.py) over the device self time a step under the
scope ``lm.moe.experts``, which also holds the pairs' gathers, the activation and what is recomputed
going backwards: they are in the time and not in the count.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module

flops_lfm2 = load_module("", "flops_lfm2")  # loading it adds the configuration's count to flops.py's table


def read(run):
    here = run["cell"]["here"]
    ms = load_module("", "scopes_lm", here).scope_ms(run, "lm.moe.experts")
    if not ms or run.get("peak") is None:
        return None
    flops = flops_lfm2
    pairs = run.get("counters", {}).get("Moe/pairs_here")
    sizes = run["config"]["sizes"]
    least_s = max(
        flops.lfm2_step_flops(sizes, pairs)["lm.moe.experts"] / run["peak"]["bf16_flops_per_s"],
        flops.lfm2_gmm_bytes(sizes, pairs) / run["peak"]["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (ms * 1e-3)
