"""Plain reference of one token-level PPO gradient step on an LFM2-MoE policy
(`https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json`,
``model_type: lfm2_moe``): straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision, no flax, no optax, nothing imported from
``sheeprl_tpu``. Experts are a plain loop over the held ones (every token through
every held expert, weighted by its routing weight or zero), attention is computed
in query blocks so that 8,192 positions fit.

It owns the weights: :func:`param_spec` lays out every leaf from the sizes in the
configuration file and :func:`make_params` draws them from the seed; the program
loads those arrays, so it runs on weights it did not make.

The model, as published:

- block: ``h = x + Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
- ``Op`` of a conv layer: ``[B, C, u] = split(W_in n, 3)``, ``z = B * u``,
  ``c_t = sum_j w_j z_{t-j}`` (depthwise, causal, ``conv_L_cache`` taps a channel),
  ``Op = W_out (C * c)``;
- ``Op`` of an attention layer: grouped-query attention, RMSNorm with a learned
  scale over each head of ``q`` and of ``k``, rotary embedding over the whole head
  (halves rotated), causal ``softmax(q k^T / sqrt(head)) v``;
- ``FFN`` of a layer under ``num_dense_layers``: ``W_2 (silu(W_1 n) * W_3 n)``;
- ``FFN`` of every other layer: ``s = sigmoid(W_r n)`` in float32, the
  ``num_experts_per_tok`` experts with the largest ``s + b``, weights
  ``s_e / (sum of the chosen s + 1e-6) * routed_scaling_factor``, the weighted sum
  of the chosen experts' gated MLPs;
- final RMSNorm, logits over the rows of the embedding.

Departures from the published description, each on purpose (the configuration
file lists them under ``assumed`` and ``reduced``):

- the chip's share: only experts ``expert_lo .. expert_lo + experts_held - 1`` of
  each expert layer are computed; what the others would have added is left out
  and the partial sum goes on to the next layer. The router keeps its
  ``num_experts`` outputs and its experts per token;
- the vocabulary is the first ``vocab`` rows; embedding and output head are tied;
- the layers are the published layers named in ``layers``;
- the expert bias ``b`` is drawn in [-0.02, 0.02], enters the selection only and
  gets no gradient;
- PPO heads: the actor's logits are the language model's; the critic is one
  linear map on the final normed state.

``quant`` is the control's hook: every matmul passes both operands through it.
``None`` is the reference; :func:`fake_fp8` is the nearest precision below bfloat16.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Quant = Optional[Callable[[jax.Array], jax.Array]]
GROUPS = ("mixers", "experts", "router", "embed", "critic")


# ----------------------------------------------------------------------------- sizes
def sizes_from(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from the configuration file's ``sizes``."""
    s = dict(cfg)
    s["head_dim"] = int(s.get("head_dim") or s["hidden_size"] // s["num_attention_heads"])
    s["expert_lo"] = int(s.get("expert_lo", 0))
    s["layers"] = [int(i) for i in s["layers"]]
    s["query_block"] = int(s.get("query_block", 512))
    return s


def layer_kinds(s: Dict[str, Any]):
    """(mixer, ffn) of each layer run: ('conv' | 'attn', 'dense' | 'moe'), by its published index."""
    return [
        ("attn" if s["layer_types"][i] == "full_attention" else "conv", "dense" if i < s["num_dense_layers"] else "moe")
        for i in s["layers"]
    ]


# --------------------------------------------------------------------------- weights
def param_spec(s: Dict[str, Any]) -> Dict[Tuple[str, ...], Tuple]:
    """Every leaf as ``path -> (shape, init)``; the paths are the program's own names."""
    d, hd = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    spec: Dict[Tuple[str, ...], Tuple] = {("embed",): ((s["vocab"], d), "embed")}
    for n, (mixer, ffn) in enumerate(layer_kinds(s)):
        p = ("layers", f"layer_{n}")
        spec[p + ("op_norm",)] = ((d,), "ones")
        spec[p + ("ffn_norm",)] = ((d,), "ones")
        if mixer == "conv":
            spec[p + ("conv", "in_proj")] = ((d, 3 * d), "normal")
            spec[p + ("conv", "filter")] = ((s["conv_L_cache"], d), "filter")
            spec[p + ("conv", "out_proj")] = ((d, d), "normal")
        else:
            spec[p + ("attn", "q")] = ((d, nq * hd), "normal")
            spec[p + ("attn", "k")] = ((d, nkv * hd), "normal")
            spec[p + ("attn", "v")] = ((d, nkv * hd), "normal")
            spec[p + ("attn", "o")] = ((nq * hd, d), "normal")
            spec[p + ("attn", "q_norm")] = ((hd,), "ones")
            spec[p + ("attn", "k_norm")] = ((hd,), "ones")
        if ffn == "dense":
            f = s["intermediate_size"]
            spec[p + ("ffn", "w1")] = ((d, f), "normal")
            spec[p + ("ffn", "w3")] = ((d, f), "normal")
            spec[p + ("ffn", "w2")] = ((f, d), "normal")
        else:
            f, e = s["moe_intermediate_size"], s["experts_held"]
            spec[p + ("moe", "router")] = ((d, s["num_experts"]), "normal")
            spec[p + ("moe", "bias")] = ((s["num_experts"],), "bias")
            spec[p + ("moe", "w1")] = ((e, d, f), "normal_e")
            spec[p + ("moe", "w3")] = ((e, d, f), "normal_e")
            spec[p + ("moe", "w2")] = ((e, f, d), "normal_e")
    spec[("final_norm",)] = ((d,), "ones")
    spec[("critic",)] = ((d, 1), "normal")
    return spec


def _draw(key, shape, init):
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "bias":  # the expert bias: small, so that it decides only near-ties
        return jax.random.uniform(key, shape, jnp.float32, -0.02, 0.02)
    if init == "embed":  # tied to the output head: logits of order one from a normed state
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-1])
    if init == "filter":
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])
    fan_in = shape[-2]
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def nest(flat: Dict[Tuple[str, ...], Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return out


def make_params(spec: Dict[Tuple[str, ...], Tuple], seed) -> Dict[str, Any]:
    keys = jax.random.split(jax.random.PRNGKey(seed), len(spec))
    return nest({path: _draw(k, shape, init) for k, (path, (shape, init)) in zip(keys, sorted(spec.items()))})


def place_experts(params: Dict[str, Any], where, s: Dict[str, Any]) -> Dict[str, Any]:
    """The same weights with every expert layer's experts renumbered: expert ``j`` of expert layer
    ``i`` is the one the seed drew as ``where[i][j]`` (``where`` [n_moe, num_experts] int, a
    permutation a row). The experts' kernels are drawn alike, so the router's outputs and the bias
    are what moves. The benchmark places the experts on the deployment's chips with it, by load."""
    layers = dict(params["layers"])
    moe = [n for n, (_, ffn) in enumerate(layer_kinds(s)) if ffn == "moe"]
    for i, n in enumerate(moe):
        layer = dict(layers[f"layer_{n}"])
        layer["moe"] = dict(layer["moe"], router=layer["moe"]["router"][:, where[i]], bias=layer["moe"]["bias"][where[i]])
        layers[f"layer_{n}"] = layer
    return dict(params, layers=layers)


def group_of(path: str) -> Optional[str]:
    """The compared group of a leaf, by its key path (``jax.tree_util.keystr``)."""
    if "'bias'" in path:
        return None  # no gradient by construction
    if "'router'" in path:
        return "router"
    if "'critic'" in path:
        return "critic"
    if "'embed'" in path or "'final_norm'" in path:
        return "embed"
    if "'conv'" in path or "'attn'" in path or "'op_norm'" in path:
        return "mixers"
    return "experts"  # dense FFN, the experts and the norm before them


# --------------------------------------------------------------------------- control
def _rounded(x: jax.Array, dtype, top: float) -> jax.Array:
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def fake_fp8(x: jax.Array) -> jax.Array:
    """The control's precision, the usual fp8 training recipe: both operands of
    every matmul rounded to float8 e4m3 (one scale a tensor, amax -> 448) and,
    through ``fake_fp8.cotangent`` on the product, the cotangent that its two
    backward matmuls consume rounded to float8 e5m2 (amax -> 57344)."""
    return x + jax.lax.stop_gradient(_rounded(x, jnp.float8_e4m3fn, 448.0) - x)


@jax.custom_vjp
def _e5m2_cotangent(y):
    return y


_e5m2_cotangent.defvjp(lambda y: (y, None), lambda _, g: (_rounded(g, jnp.float8_e5m2, 57344.0),))
fake_fp8.cotangent = _e5m2_cotangent


def mm(x, w, quant: Quant):
    if quant is None:
        return jnp.matmul(x, w, precision=HI)
    y = jnp.matmul(quant(x), quant(w), precision=HI)
    cotangent = getattr(quant, "cotangent", None)
    return y if cotangent is None else cotangent(y)


# ----------------------------------------------------------------------------- model
def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def conv_op(p, n, s, quant: Quant):
    """Gated short convolution over ``n`` [B, T, D]."""
    b, c, u = jnp.split(mm(n, p["in_proj"], quant), 3, axis=-1)
    z = b * u
    taps = p["filter"].shape[0]
    padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    t = z.shape[1]
    conv = sum(p["filter"][j] * padded[:, taps - 1 - j : taps - 1 - j + t] for j in range(taps))
    return mm(c * conv, p["out_proj"], quant)


def rope(x, theta: float):
    """Rotary embedding over the whole head of ``x`` [B, T, H, hd], halves rotated."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]  # [T, hd/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attn_op(p, n, s, quant: Quant):
    bsz, t, _ = n.shape
    nq, nkv, hd = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    eps = s["norm_eps"]
    q = mm(n, p["q"], quant).reshape(bsz, t, nq, hd)
    k = mm(n, p["k"], quant).reshape(bsz, t, nkv, hd)
    v = mm(n, p["v"], quant).reshape(bsz, t, nkv, hd)
    q = rope(rms_norm(q, p["q_norm"], eps), s["rope_theta"])
    k = rope(rms_norm(k, p["k_norm"], eps), s["rope_theta"])
    rep = nq // nkv
    q = q.reshape(bsz, t, nkv, rep, hd)
    block = min(s["query_block"], t)

    @jax.checkpoint
    def one_block(q_blk, k_all, v_all, start):
        # q_blk [B, Q, nkv, rep, hd]; keys up to the block's last position
        scores = jnp.einsum("bqgrh,bkgh->bgrqk", q_blk, k_all, precision=HI) / math.sqrt(hd)
        qpos = start + jnp.arange(q_blk.shape[1])[:, None]
        kpos = jnp.arange(k_all.shape[1])[None, :]
        scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
        return jnp.einsum("bgrqk,bkgh->bqgrh", jax.nn.softmax(scores, axis=-1), v_all, precision=HI)

    outs = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        outs.append(one_block(q[:, start:stop], k[:, :stop], v[:, :stop], start))
    out = jnp.concatenate(outs, axis=1).reshape(bsz, t, nq * hd)
    return mm(out, p["o"], quant)


def gated_mlp(x, w1, w3, w2, quant: Quant):
    return mm(jax.nn.silu(mm(x, w1, quant)) * mm(x, w3, quant), w2, quant)


def route(p, n, s, forced=None):
    """(the router's own choice of experts [N, k], the experts computed with [N, k], their weights
    [N, k]) of the rows ``n`` [N, D]: float32, never quantised. The experts computed with are the
    router's own unless ``forced`` gives others; their weights are the router's scores of them."""
    scores = jax.nn.sigmoid(jnp.matmul(n, p["router"], precision=HI))
    _, free = jax.lax.top_k(scores + jax.lax.stop_gradient(p["bias"]), s["num_experts_per_tok"])
    chosen = free if forced is None else forced
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if s["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return free, chosen, w * s["routed_scaling_factor"]


def moe_ffn(p, n, s, quant: Quant, forced=None):
    """The held experts' part of the expert layer over the rows ``n`` [N, D], and the choices the
    router makes here. ``forced`` [N, k]: compute with these experts a token in place of the router's
    own: see :func:`forward`."""
    free, chosen, w = route(p, n, s, forced)
    out = jnp.zeros_like(n)
    for e in range(s["experts_held"]):
        weight = jnp.sum(jnp.where(chosen == s["expert_lo"] + e, w, 0.0), axis=-1, keepdims=True)  # 0 where not chosen
        out = out + weight * gated_mlp(n, p["w1"][e], p["w3"][e], p["w2"][e], quant)
    return out, free


def forward(params, tokens, s, quant: Quant = None, forced=None):
    """``tokens`` [B, T] -> (final normed state [B, T, D], choices [n_moe, B*T, k]).

    ``forced`` [n_moe, B*T, k] makes every expert layer compute with the given experts a token
    instead of its own top-k. The top-k is the one discontinuity of the model: with seeded random
    weights the 32 scores of a token lie close together, and a rounding of the activations in the
    third digit moves one choice in ten (my chip runs, PR 29), after which that token's state
    differs by an expert's whole output. A comparison that is to read the arithmetic therefore
    follows the routing of the program it is compared with, and compares the routing apart: the
    choices returned are always the router's own, each layer's on the state that reached it."""
    eps = s["norm_eps"]
    x = params["embed"][tokens]
    choices = []
    moe_index = 0
    for n, (mixer, ffn) in enumerate(layer_kinds(s)):
        p = params["layers"][f"layer_{n}"]
        given = None
        if ffn == "moe":
            given = None if forced is None else forced[moe_index]
            moe_index += 1

        @jax.checkpoint
        def layer(x, p, given=given):
            normed = rms_norm(x, p["op_norm"], eps)
            h = x + (conv_op(p["conv"], normed, s, quant) if mixer == "conv" else attn_op(p["attn"], normed, s, quant))
            normed = rms_norm(h, p["ffn_norm"], eps)
            if ffn == "dense":
                return h + gated_mlp(normed, p["ffn"]["w1"], p["ffn"]["w3"], p["ffn"]["w2"], quant), None
            flat, chosen = moe_ffn(p["moe"], normed.reshape(-1, normed.shape[-1]), s, quant, given)
            return h + flat.reshape(h.shape), chosen

        x, chosen = layer(x, p)
        if chosen is not None:
            choices.append(chosen)
    return rms_norm(x, params["final_norm"], eps), jnp.stack(choices) if choices else None


def heads(params, final, actions, s, quant: Quant = None):
    """(log-prob of ``actions``, entropy, value), each [B, T], one sequence at a time."""

    @jax.checkpoint
    def one(f, a):
        logits = mm(f, params["embed"].T, quant)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
        return jnp.take_along_axis(logp, a[:, None], axis=-1)[:, 0], ent

    outs = [one(final[b], actions[b]) for b in range(final.shape[0])]
    value = mm(final, params["critic"], quant)[..., 0]
    return jnp.stack([o[0] for o in outs]), jnp.stack([o[1] for o in outs]), value


def ppo_losses(params, batch, s, quant: Quant = None, forced=None):
    """``batch``: ``tokens``, ``actions`` (int), ``logprobs``, ``advantages``, ``returns``,
    ``mask``, each [B, T]. The recipe of ``ppo_recurrent`` (no advantage
    normalisation, no value clipping, masked means)."""
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


# ------------------------------------------------------------------------- optimizer
def init_state(params) -> Dict[str, Any]:
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"params": params, "opt": {"mu": zeros, "nu": jax.tree_util.tree_map(jnp.zeros_like, params), "count": jnp.int32(0)}}


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree)))


def adamw_step(params, grads, opt, s):
    """Global-norm clipping, then AdamW (optax's order of operations)."""
    norm = global_norm(grads)
    clip = s["max_grad_norm"]
    if clip and clip > 0:
        grads = jax.tree_util.tree_map(lambda g: jnp.where(norm < clip, g, g / norm * clip), grads)
    b1, b2 = s["betas"]
    count = opt["count"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g), opt["nu"], grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)

    def new(p, m, v):
        return p - s["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + s["eps"]) + s["weight_decay"] * p)

    return jax.tree_util.tree_map(new, params, mu, nu), {"mu": mu, "nu": nu, "count": count}, norm


def train_step(state, batch, key, s, quant: Quant = None, forced=None):
    """One gradient step on ``batch``. ``key`` is the train call's key: the program
    spends it on the order of its one minibatch, which changes no mean. ``forced``: the
    routing to follow (:func:`forward`)."""
    del key
    (_, out), grads = jax.value_and_grad(ppo_losses, has_aux=True)(state["params"], batch, s, quant, forced)
    params, opt, norm = adamw_step(state["params"], grads, state["opt"], s)
    choices = out.pop("choices")
    return {"params": params, "opt": opt}, {"losses": {**out, "grad_norm": norm}, "grads": grads, "choices": choices}


def leaf_norms(tree) -> Dict[str, jax.Array]:
    return {
        jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }
