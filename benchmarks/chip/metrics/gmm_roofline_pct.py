"""The stock Pallas grouped-matmul kernels of the expert layers (megablox ``gmm`` forwards and for the
rows' cotangent, ``tgmm`` for the kernels' cotangent) against the chip's roofline: the least time the
chip could take for the products of the pairs computed here (the larger of their counted FLOPs over
the bf16 peak and their bytes over the memory's rate; flops_lfm2.py) over the device self time a step
of the Pallas kernels under the scope ``lm.moe.experts`` (the ``tpu_custom_call`` instructions of the
train program's text). The forward products run twice (each layer is computed again going backwards),
which is in the time and not in the count. ``moe_experts_roofline_pct`` reads the same count against
the whole scope, gathers and activation included.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module

flops_lfm2 = load_module("", "flops_lfm2")


def read(run):
    ms = load_module("", "scopes_lm", run["cell"]["here"]).kernel_ms(run, "lm.moe.experts")
    if not ms or run.get("peak") is None:
        return None
    pairs = run.get("counters", {}).get("Moe/pairs_here")
    sizes = run["config"]["sizes"]
    least_s = max(
        flops_lfm2.lfm2_step_flops(sizes, pairs)["lm.moe.experts"] / run["peak"]["bf16_flops_per_s"],
        flops_lfm2.lfm2_gmm_bytes(sizes, pairs) / run["peak"]["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (ms * 1e-3)
