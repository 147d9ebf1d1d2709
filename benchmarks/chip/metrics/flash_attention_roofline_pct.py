"""The stock Pallas flash-attention kernels (forward, dq, dkv) against the chip's roofline: the least
time the chip could take for what they must do a step (the larger of the counted FLOPs over the bf16
peak and the bytes over the memory's rate; flops_lfm2.py::flash_attention_least) over the device self
time a step of the Pallas kernels under the scope ``lm.attn`` (the ``tpu_custom_call`` instructions of
the train program's text). With heads of 64 the products fill half of the matrix unit's depth, so
about half is the most this can read. Nothing where the attention is not the kernel's.

Read in the ``--trace 1`` run, whose window is the traffic mix's ``trace_seconds``, whatever ``--seconds`` asks for.
"""
from common import load_module

flops_lfm2 = load_module("", "flops_lfm2")


def read(run):
    ms = load_module("", "scopes_lm", run["cell"]["here"]).kernel_ms(run, "lm.attn")
    if not ms or run.get("peak") is None:
        return None
    least = flops_lfm2.flash_attention_least(run["config"]["sizes"])
    least_s = max(least["flops"] / run["peak"]["bf16_flops_per_s"], least["bytes"] / run["peak"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
