"""Operation counts of one token-level PPO gradient step on the SmallThinker-21BA3B-Instruct cut, by part,
under the names of the scopes the program runs its parts in (``models/lm.py``'s ``SCOPES`` and ``lm.swa``;
``ppo.loss`` and ``ppo.opt`` are not counted: no matmul). A count file as ``flops.py`` describes one:
`smallthinker_step_flops`, ``UNCOUNTED``, ``LAYERS``, `kernels`.

Counting rules as ``flops.py`` and ``flops_trinity.py``, whose functions of the sizes alone are used as they are
(the pairs inside a mask: the lower triangle ``T*T/2`` a sequence in a full layer, the band ``W*W/2 + (T-W)*W``
in a sliding one; the expected (token, slot) pairs; the grouped products' bytes; the attention kernels' least
work). What differs is the block: four projections and no gate (q and o at 28 heads of 128, k and v at 4), every
layer with experts, no dense FFN and no shared expert, and a router whose product, top-k and sort run under
``lm.moe.route`` before attention has to have finished: they read the block's input.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from common import load_module

_band = load_module("", "flops_trinity", os.path.dirname(os.path.abspath(__file__)))
pairs_inside, expected_pairs, gmm_bytes, attention_least = _band.pairs_inside, _band.expected_pairs, _band.gmm_bytes, _band.attention_least

UNCOUNTED = ("ppo.loss", "ppo.opt")
# the scopes whose device time a per-layer metric adds up, by the metric's layer in BENCHMARK.json
# (the first four are shared with the other language-model configurations' count files)
LAYERS = {
    "expert layer": ("lm.moe.route", "lm.moe.experts"),
    "token mixers": ("lm.swa", "lm.attn"),
    "head and loss": ("lm.head", "ppo.loss"),
    "window attention": ("lm.swa",),
    "routing": ("lm.moe.route",),
}


def smallthinker_step_flops(s: Dict[str, Any], pairs_here: Optional[float] = None) -> Dict[str, float]:
    """FLOPs of ONE gradient step (forward and backward) at the configuration's ``sizes``, by part."""
    d, hd = int(s["hidden_size"]), int(s["head_dim"])
    nq, nkv = int(s["num_attention_heads"]), int(s["num_key_value_heads"])
    batch = float(s["batch"])
    tokens = batch * float(s["sequence"])
    kinds = [_band.MIXER_OF[s["layer_types"][i]] for i in s["layers"]]
    pairs = expected_pairs(s) if pairs_here is None else float(pairs_here)
    projections = tokens * (2 * d * nq * hd + 2 * d * nkv * hd)  # q and o; k and v

    def mixer(kind: str) -> float:
        # projections, then scores and weighted values over the pairs inside the mask, every query head
        return kinds.count(kind) * (projections + batch * 2 * pairs_inside(s, kind) * nq * hd)

    macs = {
        "lm.embed": 0.0,  # a gather
        "lm.swa": mixer("swa"),
        "lm.attn": mixer("attn"),
        "lm.moe.route": len(kinds) * tokens * d * int(s["num_experts"]),
        "lm.moe.experts": pairs * 3 * d * int(s["moe_intermediate_size"]),
        "lm.head": tokens * (d * int(s["vocab"]) + d),  # logits over the held rows, and the critic
    }
    parts = {k: 3.0 * 2.0 * v for k, v in macs.items()}  # 2 FLOPs a multiply-add, 3x forward for a trained path
    parts["total"] = sum(parts.values())
    return parts


def kernels(s: Dict[str, Any], pairs_here: Optional[float] = None) -> Dict[str, Dict[str, Any]]:
    """The program's Pallas kernels by family: the scope they run under and the least they must do a step
    (megablox ``gmm`` / ``tgmm`` for the experts' grouped products; the full layer's attention; the sliding
    layers' attention over the band, counted as ``flops_trinity.py`` counts it)."""
    return {
        "gmm": {"scope": "lm.moe.experts", "flops": smallthinker_step_flops(s, pairs_here)["lm.moe.experts"], "bytes": gmm_bytes(s, pairs_here)},
        "attention": {"scope": "lm.attn", **attention_least(s, "attn")},
        "window_attention": {"scope": "lm.swa", **attention_least(s, "swa")},
    }
