"""Plain reference of one token-level PPO gradient step on a SmallThinker-21BA3B-Instruct policy
(`https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json`; what the
config has no key for is from the published modelling code,
``transformers/models/smallthinker/modeling_smallthinker.py``, and from llama.cpp's
``llm_build_smallthinker``): straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
attention by an explicit mask in query blocks so that 16,384 positions fit, the experts a plain loop
over the held ones, no kernel, no compact buffer, nothing imported from ``sheeprl_tpu``. What is not
the model's own is ``reference/lfm2_ppo.py``'s as it stands: the control's rounding, the matmul with
its hook, the RMSNorm, the rotary embedding, AdamW with its clipping, the leaf norms.

The model, a block with input ``x`` [B, T, 2560]:

- ``r = x Wr`` in float32, on the block's input **as it is, before the input norm and before attention**;
  the chosen are the ``num_experts_per_tok`` largest of ``r``; ``w = softmax(r[chosen])`` over the
  chosen alone, which is the softmax over all experts renormalised over the chosen (the published
  ``moe_primary_router_apply_softmax`` and ``norm_topk_prob`` both true); no bias, no scale;
- ``n = RMSNorm(x)``; ``q = n Wq`` as [28, 128], ``k = n Wk``, ``v = n Wv`` as [4, 128], **no per-head norm**;
  rotary embedding (whole head, halves rotated) on ``q`` and ``k`` in the layer types that
  ``rope_layer_types`` names (the sliding ones, ``rope_layout`` 1), none in the others;
  ``a_i = softmax_j(q_i k_j / sqrt(128)) v_j`` over ``j <= i`` and, in a sliding layer,
  ``i - j < sliding_window``; ``h = x + a Wo``;
- ``m = RMSNorm(h)``; ``y = h + sum over the chosen e of w_e W2_e (relu(W1_e m) * (W3_e m))``;
- a final RMSNorm; logits over the rows of an untied head.

Departures from the published description, each on purpose (the configuration file lists them
under ``assumed`` and ``reduced``): the chip's share, the critic and ``quant`` are as in
``lfm2_ppo.py``: only experts ``expert_lo .. expert_lo + experts_held - 1`` of each expert layer are
computed and the others' part is left out, the vocabulary is the first ``vocab`` rows of the
embedding and of the head, the layers are the published layers named in ``layers``. The published
family also describes secondary experts; the config has keys for primary experts only, and the
config rules. The seeded weights' scales are :func:`param_spec`'s.
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from common import load_module

_plain = load_module("reference", "lfm2_ppo", os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HI = _plain.HI
Quant = _plain.Quant
mm, rms_norm, rope = _plain.mm, _plain.rms_norm, _plain.rope
fake_fp8 = _plain.fake_fp8
init_state, adamw_step, leaf_norms = _plain.init_state, _plain.adamw_step, _plain.leaf_norms

MIXER_OF = {"full_attention": "attn", "sliding_attention": "swa"}


# ----------------------------------------------------------------------------- sizes
def sizes_from(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from the configuration file's ``sizes``."""
    s = dict(cfg)
    s["expert_lo"] = int(s.get("expert_lo", 0))
    s["layers"] = [int(i) for i in s["layers"]]
    s["query_block"] = int(s.get("query_block", 512))
    return s


def layer_kinds(s: Dict[str, Any]):
    """(mixer, ffn) of each layer run: ('attn' | 'swa', 'moe'), by its published index: every layer has experts."""
    return [(MIXER_OF[s["layer_types"][i]], "moe") for i in s["layers"]]


# --------------------------------------------------------------------------- weights
def param_spec(s: Dict[str, Any]) -> Dict[Tuple[str, ...], Tuple]:
    """Every leaf as ``path -> (shape, init)``; the paths are the program's own names.

    The benchmark's seeded weights, not the program's own start (``lm.init_params``): the embedding's elements
    have variance 1 and the two projections that write into the residual stream (``o`` and the experts' ``w2``)
    have variance 1 / (fan_in x 2 x published layers), the scaled start of a deep stack. This model's router
    reads the residual stream un-normed. With an embedding of variance 1 / hidden_size and output projections of
    variance 1 / fan_in, one attention layer's output (over random keys the average value of the positions
    before, nearly the same vector at every position, to which Zipf ids give a mean) is fifty times the
    embedding's size, and every later router would send all tokens to the same six experts: the collapse that
    ``trinity_ppo.py`` met (PERF.md, PR 34). At these scales a token's own embedding is what the routers read,
    and a half-block adds a tenth of it."""
    d, hd = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    out = 1.0 / math.sqrt(2 * len(s["layer_types"]))  # on top of 1 / sqrt(fan_in)
    spec: Dict[Tuple[str, ...], Tuple] = {("embed",): ((s["vocab"], d), "unit"), ("head",): ((s["vocab"], d), "embed")}
    f, e = s["moe_intermediate_size"], s["experts_held"]
    for n, _ in enumerate(layer_kinds(s)):
        p = ("layers", f"layer_{n}")
        spec[p + ("op_norm",)] = ((d,), "ones")
        spec[p + ("ffn_norm",)] = ((d,), "ones")
        spec[p + ("attn", "q")] = ((d, nq * hd), "normal")
        spec[p + ("attn", "k")] = ((d, nkv * hd), "normal")
        spec[p + ("attn", "v")] = ((d, nkv * hd), "normal")
        spec[p + ("attn", "o")] = ((nq * hd, d), out)
        spec[p + ("moe", "router")] = ((d, s["num_experts"]), "normal")
        spec[p + ("moe", "w1")] = ((e, d, f), "normal_e")
        spec[p + ("moe", "w3")] = ((e, d, f), "normal_e")
        spec[p + ("moe", "w2")] = ((e, f, d), out)
    spec[("final_norm",)] = ((d,), "ones")
    spec[("critic",)] = ((d, 1), "normal")
    return spec


def make_params(spec: Dict[Tuple[str, ...], Tuple], seed) -> Dict[str, Any]:
    """``lfm2_ppo.make_params`` (a key a leaf, in the order of the sorted paths); ``"unit"`` is a normal leaf of
    variance 1 an element, and a leaf whose ``init`` is a number a normal kernel of variance 1 / fan_in times that
    number."""
    def draw(key, shape, init):
        if init == "unit":
            return jax.random.normal(key, shape, jnp.float32)
        if isinstance(init, float):
            return jax.random.normal(key, shape, jnp.float32) * (init / math.sqrt(shape[-2]))
        return _plain._draw(key, shape, init)

    keys = jax.random.split(jax.random.PRNGKey(seed), len(spec))
    return _plain.nest({path: draw(k, shape, init) for k, (path, (shape, init)) in zip(keys, sorted(spec.items()))})


def place_experts(params: Dict[str, Any], where, s: Dict[str, Any]) -> Dict[str, Any]:
    """The same weights with every expert layer's experts renumbered: expert ``j`` of expert layer ``i`` is
    the one the seed drew as ``where[i][j]``. The experts' kernels are drawn alike, so the router's outputs
    are what moves (this router has no bias)."""
    layers = dict(params["layers"])
    for n in range(len(layer_kinds(s))):  # every layer has experts
        layer = dict(layers[f"layer_{n}"])
        layer["moe"] = dict(layer["moe"], router=layer["moe"]["router"][:, where[n]])
        layers[f"layer_{n}"] = layer
    return dict(params, layers=layers)


def group_of(path: str) -> Optional[str]:
    """The compared group of a leaf, by its key path (``jax.tree_util.keystr``): `check_seq.GROUPS` as they are."""
    if "'router'" in path:
        return "router"
    if "'critic'" in path:
        return "critic"
    if "'embed'" in path or "'head'" in path or "'final_norm'" in path:
        return "embed"
    if "'attn'" in path or "'op_norm'" in path:
        return "mixers"
    return "experts"  # the experts and the norm before them


# ----------------------------------------------------------------------------- model
def attn_op(p, n, s, mixer: str, quant: Quant):
    bsz, t, _ = n.shape
    nq, nkv, hd = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    q = mm(n, p["q"], quant).reshape(bsz, t, nq, hd)
    k = mm(n, p["k"], quant).reshape(bsz, t, nkv, hd)
    v = mm(n, p["v"], quant).reshape(bsz, t, nkv, hd)
    if any(MIXER_OF[kind] == mixer for kind in s["rope_layer_types"]):
        q, k = rope(q, s["rope_theta"]), rope(k, s["rope_theta"])
    window = int(s["sliding_window"]) if mixer == "swa" else t  # a full layer sees every earlier position
    q = q.reshape(bsz, t, nkv, nq // nkv, hd)
    block = min(s["query_block"], t)

    @jax.checkpoint
    def one_block(q_blk, k_seen, v_seen, start, first):
        scores = jnp.einsum("bqgrh,bkgh->bgrqk", q_blk, k_seen, precision=HI) / math.sqrt(hd)
        i = start + jnp.arange(q_blk.shape[1])[:, None]
        j = first + jnp.arange(k_seen.shape[1])[None, :]
        scores = jnp.where((j <= i) & (i - j < window), scores, -jnp.inf)
        return jnp.einsum("bgrqk,bkgh->bqgrh", jax.nn.softmax(scores, axis=-1), v_seen, precision=HI)

    outs = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        first = max(0, start - window + 1)  # the first key that the block's first query sees
        outs.append(one_block(q[:, start:stop], k[:, first:stop], v[:, first:stop], start, first))
    return mm(jnp.concatenate(outs, axis=1).reshape(bsz, t, nq * hd), p["o"], quant)


def relu_glu(x, w1, w3, w2, quant: Quant):
    return mm(jax.nn.relu(mm(x, w1, quant)) * mm(x, w3, quant), w2, quant)


def route(p, x, s, forced=None):
    """(the router's own choice of experts [N, k], the experts computed with [N, k], their weights [N, k]) of the
    rows ``x`` [N, D] of the block's input: float32, never quantised. ``forced``: see ``lfm2_ppo.route``."""
    logits = jnp.matmul(x, p["router"], precision=HI)
    _, free = jax.lax.top_k(logits, s["num_experts_per_tok"])
    chosen = free if forced is None else forced
    if s["norm_topk_prob"]:
        w = jax.nn.softmax(jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)
    else:
        w = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), chosen, axis=-1)
    return free, chosen, w


def moe_ffn(p, m, x, s, quant: Quant, forced=None):
    """The held experts' part of the expert layer over the normed rows ``m`` [N, D], routed by the block's input
    ``x`` [N, D], and the choices the router makes here."""
    free, chosen, w = route(p, x, s, forced)
    out = jnp.zeros_like(m)
    for e in range(s["experts_held"]):
        weight = jnp.sum(jnp.where(chosen == s["expert_lo"] + e, w, 0.0), axis=-1, keepdims=True)  # 0 where not chosen
        out = out + weight * relu_glu(m, p["w1"][e], p["w3"][e], p["w2"][e], quant)
    return out, free


def forward(params, tokens, s, quant: Quant = None, forced=None):
    """``tokens`` [B, T] -> (final normed state [B, T, D], choices [n_moe, B*T, k]). ``forced`` [n_moe, B*T, k]
    makes every expert layer compute with the given experts a token (``lfm2_ppo.forward`` says why)."""
    eps = s["norm_eps"]
    x = params["embed"][tokens]
    choices = []
    for n, (mixer, _) in enumerate(layer_kinds(s)):
        p = params["layers"][f"layer_{n}"]
        given = None if forced is None else forced[n]

        @jax.checkpoint
        def layer(x, p, given=given):
            h = x + attn_op(p["attn"], rms_norm(x, p["op_norm"], eps), s, mixer, quant)
            m = rms_norm(h, p["ffn_norm"], eps)
            flat, chosen = moe_ffn(p["moe"], m.reshape(-1, m.shape[-1]), x.reshape(-1, x.shape[-1]), s, quant, given)
            return h + flat.reshape(h.shape), chosen

        x, chosen = layer(x, p)
        choices.append(chosen)
    return rms_norm(x, params["final_norm"], eps), jnp.stack(choices)


def heads(params, final, actions, s, quant: Quant = None):
    """(log-prob of ``actions``, entropy, value), each [B, T], one sequence at a time, over the untied head's rows."""
    return _plain.heads({"embed": params["head"], "critic": params["critic"]}, final, actions, s, quant)


def ppo_losses(params, batch, s, quant: Quant = None, forced=None):
    """``lfm2_ppo.ppo_losses``' recipe (no advantage normalisation, no value clipping, masked means) on this model."""
    final, choices = forward(params, batch["tokens"], s, quant, forced)
    logp, ent, value = heads(params, final, batch["actions"], s, quant)
    mask = batch["mask"]
    count = jnp.maximum(jnp.sum(mask), 1.0)
    ratio = jnp.exp(logp - batch["logprobs"])
    adv = batch["advantages"]
    clip = s["clip_coef"]
    pg = jnp.sum(-jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1.0 - clip, 1.0 + clip)) * mask) / count
    vl = jnp.sum(jnp.square(value - batch["returns"]) * mask) / count
    el = -jnp.sum(ent * mask) / count
    total = pg + s["vf_coef"] * vl + s["ent_coef"] * el
    return total, {"policy": pg, "value": vl, "entropy": el, "choices": choices}


def train_step(state, batch, key, s, quant: Quant = None, forced=None):
    """One gradient step on ``batch`` (``lfm2_ppo.train_step`` on this model's losses)."""
    del key
    (_, out), grads = jax.value_and_grad(ppo_losses, has_aux=True)(state["params"], batch, s, quant, forced)
    params, opt, norm = adamw_step(state["params"], grads, state["opt"], s)
    choices = out.pop("choices")
    return {"params": params, "opt": opt}, {"losses": {**out, "grad_norm": norm}, "grads": grads, "choices": choices}
