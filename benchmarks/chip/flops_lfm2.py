"""Operation counts of one token-level PPO gradient step on the LFM2 cut, by part,
under the names of the scopes the program runs its parts in (``models/lm.py``'s
``SCOPES``; ``ppo.loss`` and ``ppo.opt`` are not counted: no matmul). A count file
as ``flops.py`` describes one: `lfm2_step_flops`, ``UNCOUNTED``, ``LAYERS``, `kernels`.

Counting rules as ``flops.py``: a matmul [m,k]@[k,n] is 2*m*k*n; a trained path
costs 3x its forward; causal attention counts the lower triangle (half of
[T, T]); recomputed operations, norms, activations, the softmax, the rotary
embedding, the 3-tap convolution itself and the routing's sort are not counted.

The experts' part is counted from the (token, slot) pairs that were computed on
this chip: ``pairs_here`` a step where a reader has the program's
``Moe/pairs_here``, else the expectation (tokens x experts per token x experts
held / experts, a layer).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

UNCOUNTED = ("ppo.loss", "ppo.opt")
# the scopes whose device time a shared per-layer metric adds up, by the metric's layer in BENCHMARK.json
LAYERS = {
    "expert layer": ("lm.moe.route", "lm.moe.experts"),
    "token mixers": ("lm.conv", "lm.attn"),
    "head and loss": ("lm.head", "ppo.loss"),
}


def _kinds(s: Dict[str, Any]):
    return [
        ("attn" if s["layer_types"][i] == "full_attention" else "conv", "dense" if i < s["num_dense_layers"] else "moe")
        for i in s["layers"]
    ]


def expected_pairs(s: Dict[str, Any]) -> float:
    """Pairs computed here a step by expectation, all expert layers together."""
    tokens = float(s["batch"] * s["sequence"])
    n_moe = sum(1 for _, ffn in _kinds(s) if ffn == "moe")
    return n_moe * tokens * s["num_experts_per_tok"] * s["experts_held"] / s["num_experts"]


def lfm2_step_flops(s: Dict[str, Any], pairs_here: Optional[float] = None) -> Dict[str, float]:
    """FLOPs of ONE gradient step (forward and backward) at the configuration's ``sizes``, by part."""
    d = int(s["hidden_size"])
    hd = int(s.get("head_dim") or d // s["num_attention_heads"])
    nq, nkv = int(s["num_attention_heads"]), int(s["num_key_value_heads"])
    t = float(s["sequence"])
    tokens = float(s["batch"]) * t
    kinds = _kinds(s)
    n_conv = sum(1 for m, _ in kinds if m == "conv")
    n_attn = len(kinds) - n_conv
    n_dense = sum(1 for _, f in kinds if f == "dense")
    n_moe = len(kinds) - n_dense
    pairs = expected_pairs(s) if pairs_here is None else float(pairs_here)

    macs = {
        "lm.embed": 0.0,  # a gather
        "lm.conv": n_conv * tokens * (d * 3 * d + d * d),
        # projections, then scores and weighted values over the lower triangle: 2 x (T/2) x heads x head size a token
        "lm.attn": n_attn * tokens * (d * nq * hd + 2 * d * nkv * hd + nq * hd * d + 2 * (t / 2) * nq * hd),
        "lm.dense_ffn": n_dense * tokens * 3 * d * int(s["intermediate_size"]),
        "lm.moe.route": n_moe * tokens * d * int(s["num_experts"]),
        "lm.moe.experts": pairs * 3 * d * int(s["moe_intermediate_size"]),
        "lm.head": tokens * (d * int(s["vocab"]) + d),  # logits over the held rows, and the critic
    }
    parts = {k: 3.0 * 2.0 * v for k, v in macs.items()}  # 2 FLOPs a multiply-add, 3x forward for a trained path
    parts["total"] = sum(parts.values())
    return parts


def lfm2_gmm_bytes(s: Dict[str, Any], pairs_here: Optional[float] = None, bytes_per: int = 2) -> float:
    """Bytes the experts' grouped products must move a step, forwards and backwards: every held expert's
    three kernels read once forwards and once backwards and their gradients written once, and each pair's
    rows in and out of the three products."""
    d, f = int(s["hidden_size"]), int(s["moe_intermediate_size"])
    n_moe = sum(1 for _, ffn in _kinds(s) if ffn == "moe")
    pairs = expected_pairs(s) if pairs_here is None else float(pairs_here)
    weights = n_moe * int(s["experts_held"]) * 3 * d * f * bytes_per
    rows = pairs * (2 * d + 3 * f) * bytes_per
    return 3.0 * weights + 3.0 * rows


def flash_attention_least(s: Dict[str, Any]) -> Dict[str, float]:
    """What the flash-attention kernels (forward, dq, dkv) must do a step: FLOPs of the causal
    half of two products forwards and four backwards (scores and weighted values; the gradients of
    the values, the probabilities, the keys and the queries; the scores the backward kernels make
    again are not counted), and the bytes of q, k, v, the output and their four cotangents, each
    once, with keys and values at the query heads' count as the kernel is given them."""
    hd = int(s.get("head_dim") or int(s["hidden_size"]) // int(s["num_attention_heads"]))
    nq, t, b = int(s["num_attention_heads"]), float(s["sequence"]), float(s["batch"])
    n_attn = sum(1 for m, _ in _kinds(s) if m == "attn")
    return {
        "flops": n_attn * 6 * 2.0 * b * nq * (t * t / 2) * hd,
        "bytes": n_attn * 8 * b * nq * t * hd * 2.0,
    }


def kernels(s: Dict[str, Any], pairs_here: Optional[float] = None) -> Dict[str, Dict[str, Any]]:
    """The program's Pallas kernels by family: the scope they run under and the least they must do a step
    (megablox ``gmm`` / ``tgmm`` for the experts' grouped products, flash attention forward, dq and dkv)."""
    return {
        "gmm": {"scope": "lm.moe.experts", "flops": lfm2_step_flops(s, pairs_here)["lm.moe.experts"], "bytes": lfm2_gmm_bytes(s, pairs_here)},
        "attention": {"scope": "lm.attn", **flash_attention_least(s)},
    }
