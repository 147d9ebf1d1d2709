"""A language-model policy: the LFM2-MoE block family as pure functions over a
parameter dict (``https://huggingface.co/LiquidAI/LFM2-8B-A1B``, ``model_type:
lfm2_moe``).

Block: ``h = x + Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``. ``Op`` is a gated
short convolution (``conv``) or grouped-query attention with per-head RMSNorm on
``q`` and ``k`` and a rotary embedding (``full_attention``); ``FFN`` is a gated
MLP in the leading ``num_dense_layers`` published layers and a sigmoid-routed
expert layer in the others. After the last block an RMSNorm; the logits are over
the held rows of the (tied) embedding; the PPO critic is one linear map on the
final normed state.

**The chip's share.** The expert layer is the one expert parallelism asks for:
told which experts it holds (``expert_lo``, ``experts_held``), it routes over all
``num_experts``, computes its own experts' part of the result and leaves the
other experts' part out. No token is dropped and there is no capacity factor: the
(token, slot) pairs routed to the held experts are sorted by expert, in front of
the others, and are multiplied as ragged groups (:func:`grouped_dot`). Rows are
moved only for the pairs held here: the buffer between the routing and the
products has :func:`compact_rows` rows, :data:`SLACK` times the held experts' even
share of all pairs in whole row tiles of the grouped matmul, a width fixed by the
shapes (no option sets it). How many pairs are held is the data's, so a layer
whose held pairs pass that width takes the width of all pairs instead, under a
``lax.cond`` (:func:`_held_experts`): the same result at the older speed, which is
what keeps the layer dropless. ``Moe/compact_share`` (:func:`moe_metrics`) is the
share of the expert layers that stayed within the buffer; under 1.0 it says that
this chip's experts draw half as many pairs again as an even share, and the cure
is the placement (deal the experts to the chips by their load, as the benchmark's
set-up does), not a wider buffer. On a TPU the grouped products
and the attention over whole sequences are the stock Pallas kernels (megablox
``gmm`` / ``tgmm``, flash attention); elsewhere ``jax.lax.ragged_dot`` and a
blocked plain-JAX attention compute the same (:func:`on_tpu`). ``vocab_held`` is the slice of
the vocabulary held here, ``layers`` the published layers that are run.

Two ways through the same weights: :func:`forward` / :func:`evaluate` over whole
sequences that start at position 0 (training), and :func:`decode_step`, one token
a sequence through the carried state (:func:`init_state`: the last
``conv_L_cache - 1`` gated inputs of each conv layer, keys and values of each
attention layer, and the position).

Every part runs under a ``jax.named_scope`` of :data:`SCOPES`; the benchmark's
FLOP count uses the same names.
"""

from __future__ import annotations

import functools
import importlib
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# the parts the benchmark's flops_lfm2.py counts, by the scope they run under
SCOPES = ("lm.embed", "lm.conv", "lm.attn", "lm.dense_ffn", "lm.moe.route", "lm.moe.experts", "lm.head")
HI = jax.lax.Precision.HIGHEST
# What a block keeps for its backward pass beside its input (`_layer`): its matmul products, marked with this
# name where they are made. Outside a `jax.checkpoint` the mark is the identity.
PRODUCT = "lm.product"
KEEP_PRODUCTS = jax.checkpoint_policies.save_only_these_names(PRODUCT)


@dataclass(frozen=True)
class LMConfig:
    hidden_size: int
    layers: Tuple[int, ...]  # published layer indices that are run
    layer_types: Tuple[str, ...]  # of every published layer: "conv" | "full_attention"
    num_dense_layers: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int  # the router's outputs
    num_experts_per_tok: int
    experts_held: int
    vocab_held: int
    num_attention_heads: int
    num_key_value_heads: int
    max_positions: int
    expert_lo: int = 0
    head_dim: Optional[int] = None
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    conv_L_cache: int = 3
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    query_block: int = 512  # queries a block of the plain-JAX attention; the flash kernel's blocks are twice this
    head_chunk: int = 2048  # positions of one sequence whose logits exist at a time

    @property
    def head(self) -> int:
        return int(self.head_dim or self.hidden_size // self.num_attention_heads)

    @property
    def kinds(self):
        """(mixer, ffn) of each layer that is run, by its published index."""
        return tuple(
            ("attn" if self.layer_types[i] == "full_attention" else "conv", "dense" if i < self.num_dense_layers else "moe")
            for i in self.layers
        )

    @classmethod
    def from_cfg(cls, lm: Any) -> "LMConfig":
        """From the ``algo.lm`` group of the config tree."""
        get = lm.get if hasattr(lm, "get") else lambda k, d=None: getattr(lm, k, d)
        return cls(
            hidden_size=int(lm["hidden_size"]),
            layers=tuple(int(i) for i in lm["layers"]),
            layer_types=tuple(str(t) for t in lm["layer_types"]),
            num_dense_layers=int(lm["num_dense_layers"]),
            intermediate_size=int(lm["intermediate_size"]),
            moe_intermediate_size=int(lm["moe_intermediate_size"]),
            num_experts=int(lm["num_experts"]),
            num_experts_per_tok=int(lm["num_experts_per_tok"]),
            experts_held=int(lm["experts_held"]),
            expert_lo=int(get("expert_lo", 0) or 0),
            vocab_held=int(lm["vocab_held"]),
            num_attention_heads=int(lm["num_attention_heads"]),
            num_key_value_heads=int(lm["num_key_value_heads"]),
            head_dim=get("head_dim", None),
            rope_theta=float(lm["rope_theta"]),
            norm_eps=float(lm["norm_eps"]),
            conv_L_cache=int(lm["conv_L_cache"]),
            norm_topk_prob=bool(lm["norm_topk_prob"]),
            routed_scaling_factor=float(lm["routed_scaling_factor"]),
            max_positions=int(lm["max_positions"]),
            query_block=int(get("query_block", 512) or 512),
            head_chunk=int(get("head_chunk", 2048) or 2048),
        )


# --------------------------------------------------------------------------- weights
def param_shapes(cfg: LMConfig) -> Dict[Tuple[str, ...], Tuple[Tuple[int, ...], str]]:
    """``path -> (shape, init)`` of every leaf."""
    d, hd = cfg.hidden_size, cfg.head
    nq, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    spec: Dict[Tuple[str, ...], Tuple] = {("embed",): ((cfg.vocab_held, d), "embed")}
    for n, (mixer, ffn) in enumerate(cfg.kinds):
        p = ("layers", f"layer_{n}")
        spec[p + ("op_norm",)] = ((d,), "ones")
        spec[p + ("ffn_norm",)] = ((d,), "ones")
        if mixer == "conv":
            spec[p + ("conv", "in_proj")] = ((d, 3 * d), "normal")
            spec[p + ("conv", "filter")] = ((cfg.conv_L_cache, d), "normal")
            spec[p + ("conv", "out_proj")] = ((d, d), "normal")
        else:
            spec[p + ("attn", "q")] = ((d, nq * hd), "normal")
            spec[p + ("attn", "k")] = ((d, nkv * hd), "normal")
            spec[p + ("attn", "v")] = ((d, nkv * hd), "normal")
            spec[p + ("attn", "o")] = ((nq * hd, d), "normal")
            spec[p + ("attn", "q_norm")] = ((hd,), "ones")
            spec[p + ("attn", "k_norm")] = ((hd,), "ones")
        if ffn == "dense":
            f = cfg.intermediate_size
            spec[p + ("ffn", "w1")] = ((d, f), "normal")
            spec[p + ("ffn", "w3")] = ((d, f), "normal")
            spec[p + ("ffn", "w2")] = ((f, d), "normal")
        else:
            f, e = cfg.moe_intermediate_size, cfg.experts_held
            spec[p + ("moe", "router")] = ((d, cfg.num_experts), "normal")
            spec[p + ("moe", "bias")] = ((cfg.num_experts,), "bias")
            spec[p + ("moe", "w1")] = ((e, d, f), "normal")
            spec[p + ("moe", "w3")] = ((e, d, f), "normal")
            spec[p + ("moe", "w2")] = ((e, f, d), "normal")
    spec[("final_norm",)] = ((d,), "ones")
    spec[("critic",)] = ((d, 1), "normal")
    return spec


def init_params(cfg: LMConfig, key: jax.Array) -> Dict[str, Any]:
    """Float32 weights from ``key``: normal kernels of variance 1/fan_in, an embedding
    of variance 1/hidden, norm scales 1, the expert bias uniform in [-0.02, 0.02]."""
    spec = param_shapes(cfg)
    out: Dict[str, Any] = {}
    for k, (path, (shape, init)) in zip(jax.random.split(key, len(spec)), sorted(spec.items())):
        if init == "ones":
            leaf = jnp.ones(shape, jnp.float32)
        elif init == "bias":
            leaf = jax.random.uniform(k, shape, jnp.float32, -0.02, 0.02)
        elif init == "embed":  # tied to the output head: logits of order one from a normed state
            leaf = jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[-1])
        else:
            leaf = jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[-2])
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return out


_FLOAT32_LEAVES = ("router", "bias", "op_norm", "ffn_norm", "final_norm", "q_norm", "k_norm")


def working_copy(params: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The matmul operands once in ``dtype``; the router, its bias and the norm scales stay float32."""

    def cast(path, w):
        name = getattr(path[-1], "key", None)
        return w if name in _FLOAT32_LEAVES else w.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, params)


# ----------------------------------------------------------------------------- parts
def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps) * scale
    return y.astype(x.dtype)


def _rope_tables(positions: jax.Array, hd: int, theta: float):
    """cos, sin of shape ``positions.shape + (hd,)``, halves repeated."""
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.concatenate([jnp.cos(ang)] * 2, -1), jnp.concatenate([jnp.sin(ang)] * 2, -1)


def _rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``x`` [..., H, hd] rotated; ``cos`` / ``sin`` broadcast over the heads."""
    hd = x.shape[-1]
    x32 = x.astype(jnp.float32)
    rotated = jnp.concatenate([-x32[..., hd // 2 :], x32[..., : hd // 2]], -1)
    return (x32 * cos[..., None, :] + rotated * sin[..., None, :]).astype(x.dtype)


def conv_op(p: Dict[str, jax.Array], n: jax.Array, tail: Optional[jax.Array] = None):
    """Gated short convolution over ``n`` [B, T, D]. ``tail`` [B, L-1, D] is the
    gated input of the positions before (zeros at a sequence's start). Returns
    the output and the new tail."""
    with jax.named_scope("lm.conv"):
        b, c, u = jnp.split(checkpoint_name(n @ p["in_proj"], PRODUCT), 3, axis=-1)
        z = b * u
        taps = p["filter"].shape[0]
        t = z.shape[1]
        if tail is None:
            tail = jnp.zeros((z.shape[0], taps - 1, z.shape[2]), z.dtype)
        padded = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
        conv = sum(p["filter"][j].astype(z.dtype) * padded[:, taps - 1 - j : taps - 1 - j + t] for j in range(taps))
        return checkpoint_name((c * conv) @ p["out_proj"], PRODUCT), padded[:, t:]


def _qkv(p, n, cfg: LMConfig, positions):
    bsz, t, _ = n.shape
    nq, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head
    q = checkpoint_name(n @ p["q"], PRODUCT).reshape(bsz, t, nq, hd)
    k = checkpoint_name(n @ p["k"], PRODUCT).reshape(bsz, t, nkv, hd)
    v = checkpoint_name(n @ p["v"], PRODUCT).reshape(bsz, t, nkv, hd)
    cos, sin = _rope_tables(positions, hd, cfg.rope_theta)
    q = _rope(rms_norm(q, p["q_norm"], cfg.norm_eps), cos, sin)
    k = _rope(rms_norm(k, p["k_norm"], cfg.norm_eps), cos, sin)
    return q, k, v


def _attend(q, k, v, allowed):
    """``q`` [B, Q, G, R, hd], ``k`` / ``v`` [B, K, G, hd], ``allowed`` broadcastable to [B, 1, 1, Q, K]."""
    scores = jnp.einsum("bqgrh,bkgh->bgrqk", q, k, preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgrqk,bkgh->bqgrh", probs.astype(v.dtype), v)


def _flash_call(q, k, v, block: int):
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes, flash_attention

    blocks = BlockSizes(
        block_q=block, block_k_major=block, block_k=block, block_b=1, block_q_major_dkv=block, block_k_major_dkv=block,
        block_k_dkv=block, block_q_dkv=block, block_k_major_dq=block, block_k_dq=block, block_q_dq=block,
    )
    return flash_attention(q, k, v, causal=True, sm_scale=1.0 / math.sqrt(q.shape[-1]), block_sizes=blocks)


# The kernels' products take the precision that is the default where they are traced, and Mosaic
# has no "high" (the CLI's default for float32 matmuls): they are traced, forwards and backwards,
# under "default", which for their bfloat16 operands is the same arithmetic.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, block):
    with jax.default_matmul_precision("default"):
        return _flash_call(q, k, v, block)


def _flash_fwd(q, k, v, block):
    with jax.default_matmul_precision("default"):
        return jax.vjp(functools.partial(_flash_call, block=block), q, k, v)


def _flash_bwd(block, vjp, g):
    with jax.default_matmul_precision("default"):
        return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def on_tpu() -> bool:
    """Whether whole-sequence attention goes through the Pallas kernel: it is a TPU kernel, and the
    passes that use it (training, scoring) run on the default backend's devices. Everywhere else
    (the CPU, where the tests run) the plain-JAX blocked form computes the same attention."""
    return jax.default_backend() == "tpu"


def flash_core(q: jax.Array, k: jax.Array, v: jax.Array, cfg: LMConfig) -> jax.Array:
    """Causal attention by the stock Pallas TPU flash-attention kernel (its own
    ``custom_vjp``): ``q`` [B, T, H, hd], ``k`` / ``v`` [B, T, G, hd] -> [B, T, H * hd].
    The kernel has no grouped-query form, so each key-value head is repeated for
    the query heads it serves. Its default blocks of 128 leave it five times
    slower than blocks of 1,024 at 8,192 positions (my chip runs, PR 29)."""
    bsz, t, nq, hd = q.shape
    rep = nq // k.shape[2]
    heads_first = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    kq, vq = (jnp.repeat(heads_first(x), rep, axis=1) for x in (k, v))
    out = _flash(heads_first(q), kq, vq, min(2 * cfg.query_block, t))
    return heads_first(out).reshape(bsz, t, nq * hd)


def attn_op(p: Dict[str, jax.Array], n: jax.Array, cfg: LMConfig):
    """Causal grouped-query attention over whole sequences ``n`` [B, T, D] that
    start at position 0. The [T, T] scores never exist: on a TPU the Pallas flash
    kernel computes it, elsewhere plain JAX takes a block of queries at a time
    against the keys up to the block's end. Returns the output, keys, values."""
    with jax.named_scope("lm.attn"):
        bsz, t, _ = n.shape
        nq, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head
        q, k, v = _qkv(p, n, cfg, jnp.arange(t)[None, :])
        if on_tpu():
            return checkpoint_name(flash_core(q, k, v, cfg) @ p["o"], PRODUCT), k, v
        q = q.reshape(bsz, t, nkv, nq // nkv, hd)
        block = min(cfg.query_block, t)

        def one_block(q_blk, k_seen, v_seen, start):
            qpos = start + jnp.arange(q_blk.shape[1])[:, None]
            return _attend(q_blk, k_seen, v_seen, jnp.arange(k_seen.shape[1])[None, :] <= qpos)

        one_block = jax.checkpoint(one_block, static_argnums=(3,))  # a block's scores are made again going backwards
        outs = [
            one_block(q[:, start : start + block], k[:, : start + block], v[:, : start + block], start)
            for start in range(0, t, block)
        ]
        out = jnp.concatenate(outs, axis=1).reshape(bsz, t, nq * hd)
        return checkpoint_name(out @ p["o"], PRODUCT), k, v


def attn_decode(p, n, cfg: LMConfig, cache_k, cache_v, pos):
    """One position a sequence: ``n`` [B, 1, D], caches [B, P, G, hd], ``pos`` [B]."""
    with jax.named_scope("lm.attn"):
        bsz = n.shape[0]
        nq, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head
        q, k, v = _qkv(p, n, cfg, pos[:, None])
        rows = jnp.arange(bsz)
        cache_k = cache_k.at[rows, pos].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, pos].set(v[:, 0].astype(cache_v.dtype))
        q = q.reshape(bsz, 1, nkv, nq // nkv, hd)
        allowed = (jnp.arange(cache_k.shape[1])[None, :] <= pos[:, None])[:, None, None, None, :]
        out = _attend(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), allowed)
        return out.reshape(bsz, 1, nq * hd) @ p["o"], cache_k, cache_v


def gated_mlp(p: Dict[str, jax.Array], n: jax.Array) -> jax.Array:
    with jax.named_scope("lm.dense_ffn"):
        gate, up = checkpoint_name(n @ p["w1"], PRODUCT), checkpoint_name(n @ p["w3"], PRODUCT)
        return (jax.nn.silu(gate) * up) @ p["w2"]  # no mark: nothing going backwards needs this product


def route(p: Dict[str, jax.Array], x: jax.Array, cfg: LMConfig):
    """(chosen experts [N, k], their weights [N, k] float32) of the rows ``x`` [N, D].
    The matmul and the sigmoid are float32; the bias enters the selection only."""
    with jax.named_scope("lm.moe.route"):
        scores = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32), p["router"].astype(jnp.float32), precision=HI))
        _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(p["bias"].astype(jnp.float32)), cfg.num_experts_per_tok)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
        return chosen, w * cfg.routed_scaling_factor


def _sum_of_slots(rows: jax.Array, slots: jax.Array, here: jax.Array, weight: Optional[jax.Array] = None) -> jax.Array:
    """``out[n] = sum_s rows[slots[n, s]]`` over the slots ``s`` where ``here[n, s]``, each times ``weight[n, s]``:
    ``rows`` [R, D], the others [N, k] -> [N, D]. A gather of N rows a slot, added up in float32 and
    rounded once, as ``jnp.sum`` does; one gather of N * k rows reshaped to [N, k, D] is the same sum,
    but the chip lays k = 4 on a tiled axis and the reshape is a copy of every row (my chip runs, PR 30)."""
    total = None
    for s in range(slots.shape[1]):
        term = jnp.where(here[:, s, None], rows[slots[:, s]], 0)
        if weight is not None:
            term = term * weight[:, s, None]
        term = term.astype(jnp.float32)
        total = term if total is None else total + term
    return total.astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_to_pairs(x: jax.Array, order: jax.Array, inverse: jax.Array, rows: int) -> jax.Array:
    """``x`` [N, D] -> a row for each of the first ``rows`` (token, slot) pairs in sorted order
    [rows, D]. Going backwards a pair finds its row's cotangent by the inverse permutation (the
    pairs sorted past ``rows`` have none) and a token's slots are summed: a gather and a sum,
    where the gather's own transpose would scatter-add."""
    return x[order[:rows] // (order.shape[0] // x.shape[0])]


def _rows_to_pairs_fwd(x, order, inverse, rows):
    return _rows_to_pairs(x, order, inverse, rows), (inverse, x.shape[0])


def _rows_to_pairs_bwd(rows, res, g):
    inverse, n_rows = res
    slots = inverse.reshape(n_rows, -1)
    return _sum_of_slots(g, jnp.minimum(slots, rows - 1), slots < rows), None, None


_rows_to_pairs.defvjp(_rows_to_pairs_fwd, _rows_to_pairs_bwd)


@jax.custom_vjp
def _pairs_to_rows(ys: jax.Array, weight: jax.Array, order: jax.Array, inverse: jax.Array, held: jax.Array) -> jax.Array:
    """The sorted pairs' results ``ys`` [rows, D] summed into their tokens' rows [N, D], each by its
    routing weight: ``weight`` and ``held`` are [N, k], and a pair that is not held adds nothing,
    whatever the row its clamped index finds holds. Going backwards nothing is as wide as all
    pairs but the weights' cotangent, a number a pair: row ``j`` gets its weight times its token's
    cotangent (a gather of ``rows`` rows), and its weight's cotangent is the two rows' dot product."""
    return _sum_of_slots(ys, jnp.minimum(inverse, ys.shape[0] - 1).reshape(held.shape), held, weight)


def _pairs_to_rows_fwd(ys, weight, order, inverse, held):
    return _pairs_to_rows(ys, weight, order, inverse, held), (ys, weight, order, inverse, held)


def _pairs_to_rows_bwd(res, g):
    ys, weight, order, inverse, held = res
    rows = ys.shape[0]
    first = order[:rows]
    g_rows = g[first // held.shape[1]]
    d_ys = g_rows * weight.reshape(-1)[first][:, None]
    dots = jnp.sum(ys.astype(jnp.float32) * g_rows.astype(jnp.float32), axis=-1)
    d_weight = jnp.where(held, dots[jnp.minimum(inverse, rows - 1)].reshape(held.shape), 0)
    return d_ys.astype(ys.dtype), d_weight.astype(weight.dtype), None, None, None


_pairs_to_rows.defvjp(_pairs_to_rows_fwd, _pairs_to_rows_bwd)


GMM_TILES = (512, 1024, 1024)  # rows, contraction, columns of the Pallas grouped matmul; its default 128s are 8x slower (my chip runs, PR 29)
# The room of the compact row buffer over an even share of the pairs (`compact_rows`). As drawn, a chip's share of
# a layer's pairs had a standard deviation of 3.3% around its even quarter; placed by load it starts within 0.6%
# of it and falls 15% over a window as the routers train (PERF.md, PR 29): half as much again is out of reach of
# either, and a layer that passes it all the same takes the full width (`_held_experts`), slower and exact.
SLACK = 1.5


def compact_rows(n_pairs: int, held_n: int, num_experts: int) -> int:
    """Rows of the buffer between the routing and the grouped products: ``SLACK`` times the
    even share of ``n_pairs`` that ``held_n`` of ``num_experts`` experts get, in whole row tiles
    of the grouped matmul, and never more than all pairs (every expert held; a decode step)."""
    tile = GMM_TILES[0]
    return min(n_pairs, -(-math.ceil(SLACK * n_pairs * held_n / num_experts) // tile) * tile)


def _group_products(xs, w, group_sizes, cotangent=None):
    """The three grouped products of an expert's kernel: forwards ``xs`` [M, K] x ``w`` [G, K, N]
    (rows in consecutive groups, group ``g`` times ``w[g]``), and with a ``cotangent`` [M, N] the
    rows' and the kernels' cotangents.

    On a TPU they are the stock Pallas grouped matmul (megablox ``gmm`` / ``tgmm``), traced under the
    default matmul precision (Mosaic has no "high", and the operands are bfloat16). ``jax.lax.ragged_dot``
    is not used there: XLA:TPU computes it wrongly inside a differentiated program when the groups leave
    rows uncovered (my chip runs, PR 29: the forward product of the program that also holds the
    transposes is 93% off the same product computed alone, and alone its transpose with respect to the
    rows is 14-99% off). Elsewhere (the CPU, where the tests run) ``ragged_dot`` computes the same."""
    if on_tpu():
        # the kernels' module, by its path: the package's own name `gmm` is its differentiable wrapper
        megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
        # the kernels take whole tiles of rows: zero rows are added after the last group (a decode step has 8 rows)
        rows = xs.shape[0]
        whole = lambda a: jnp.pad(a, ((0, -rows % GMM_TILES[0]), (0, 0)))  # noqa: E731
        with jax.default_matmul_precision("default"):
            if cotangent is None:
                return megablox.gmm(whole(xs), w, group_sizes, xs.dtype, GMM_TILES)[:rows]
            d_xs = megablox.gmm(whole(cotangent), w, group_sizes, xs.dtype, GMM_TILES, transpose_rhs=True)[:rows]
            d_w = megablox.tgmm(
                whole(xs).swapaxes(0, 1), whole(cotangent), group_sizes, w.dtype, GMM_TILES, num_actual_groups=w.shape[0]
            )
            return d_xs, d_w
    if cotangent is None:
        return jax.lax.ragged_dot(xs, w, group_sizes)
    d_xs = jax.lax.ragged_dot(cotangent, jnp.swapaxes(w, 1, 2), group_sizes)
    d_w = jax.vjp(lambda kernels: jax.lax.ragged_dot(xs, kernels, group_sizes), w)[1](cotangent)[0]
    return d_xs, d_w


@jax.custom_vjp
def grouped_dot(xs: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """Rows ``xs`` [M, K] in consecutive groups, group ``g`` times ``w[g]`` [G, K, N]. The groups need
    not cover every row: the rows after the last group come out as they may, not-a-number included
    (the caller masks them), and take no part going backwards."""
    return _group_products(xs, w, group_sizes)


def _grouped_dot_fwd(xs, w, group_sizes):
    return _group_products(xs, w, group_sizes), (xs, w, group_sizes)


def _grouped_dot_bwd(res, g):
    xs, w, group_sizes = res
    covered = (jnp.arange(xs.shape[0]) < jnp.sum(group_sizes))[:, None]
    d_xs, d_w = _group_products(xs, w, group_sizes, jnp.where(covered, g, 0))
    return jnp.where(covered, d_xs, 0).astype(xs.dtype), d_w.astype(w.dtype), None


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


@functools.partial(jax.jit, static_argnames="rows")
def _experts_at_width(x, weight, kernels, routing, rows: int):
    """The held experts' partial sum [N, D] through a buffer of the first ``rows`` sorted pairs,
    which has to hold every held pair. Under ``jit`` (as :func:`_experts_at_width_vjp`) so that the
    expert layers of a model, alike in their shapes, are traced once between them: a program traces
    two widths forwards and backwards, and a launch pays for each trace (2 s a layer, my chip runs, PR 30)."""
    w1, w3, w2 = kernels
    order, inverse, held, group_sizes = routing
    xs = _rows_to_pairs(x, order, inverse, rows)  # the groups cover the held pairs, in front
    hidden = jax.nn.silu(grouped_dot(xs, w1, group_sizes)) * grouped_dot(xs, w3, group_sizes)
    return _pairs_to_rows(grouped_dot(hidden, w2, group_sizes), weight, order, inverse, held)


@functools.partial(jax.jit, static_argnames="rows")
def _experts_at_width_vjp(x, weight, kernels, routing, g, rows: int):
    """The cotangents of ``x``, ``weight`` and ``kernels`` for the cotangent ``g`` of :func:`_experts_at_width`,
    which is computed again for it."""
    return jax.vjp(lambda *inputs: _experts_at_width(*inputs, routing, rows=rows), x, weight, kernels)[1](g)


def _at_either_width(rows: int, routing, at_width):
    """``at_width(rows)`` where the held pairs fit the compact buffer, else ``at_width(all pairs)``:
    the branch follows the data, and where ``rows`` is all pairs there is none."""
    order, _, _, group_sizes = routing
    if rows == order.shape[0]:
        return at_width(rows)
    return jax.lax.cond(jnp.sum(group_sizes) <= rows, lambda: at_width(rows), lambda: at_width(order.shape[0]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _held_experts(x, weight, kernels, routing, rows: int):
    """:func:`_experts_at_width` at ``rows`` where the held pairs fit, else at the width of all
    pairs: dropless at any load. One ``custom_vjp`` whose residuals are its inputs and whose two
    rules each choose the width for themselves, so that nothing is differentiated through the
    ``cond`` (which would have the compact branch write the full width's residuals as zeros, over
    1 GB a layer at the benchmark's sizes) and a layer computed again going backwards
    (``jax.checkpoint``) does not run the products a third time."""
    return _at_either_width(rows, routing, lambda r: _experts_at_width(x, weight, kernels, routing, rows=r))


def _held_experts_fwd(x, weight, kernels, routing, rows):
    return _held_experts(x, weight, kernels, routing, rows), (x, weight, kernels, routing)


def _held_experts_bwd(rows, res, g):
    x, weight, kernels, routing = res
    return (*_at_either_width(rows, routing, lambda r: _experts_at_width_vjp(x, weight, kernels, routing, g, rows=r)), None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def moe_ffn(p: Dict[str, jax.Array], x: jax.Array, cfg: LMConfig):
    """The held experts' part of the expert layer over the rows ``x`` [N, D].

    Routing is over all ``num_experts``; the (token, slot) pairs whose expert is
    held here are sorted by expert, in front of the others, and multiplied as
    ragged groups; the others are left out. The rows between the routing and the
    products are those of the first :func:`compact_rows` sorted pairs (24,576 of
    65,536 with 8 of 32 experts held and 16,384 tokens): a static width, from the
    shapes alone. The number of held pairs is the data's, and a layer whose held
    pairs do not fit takes the width of all pairs instead: no token is dropped or
    capped at any load, the step is only slower. Returns the partial sum [N, D],
    the choices [N, k] and the layer's counters, ``compact`` (1 where the held
    pairs fitted) among them."""
    lo = cfg.expert_lo
    held_n = p["w1"].shape[0]
    n_rows, k = x.shape[0], cfg.num_experts_per_tok
    chosen, w = route(p, x, cfg)
    with jax.named_scope("lm.moe.route"):
        local = chosen - lo
        held = (local >= 0) & (local < held_n)
        sort_key = jnp.where(held, local, held_n).reshape(-1)  # pairs of absent experts go last
        order = jnp.argsort(sort_key, stable=True)
        inverse = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
        group_sizes = jnp.sum(sort_key[:, None] == jnp.arange(held_n)[None, :], axis=0, dtype=jnp.int32)
    rows_wide = compact_rows(n_rows * k, held_n, cfg.num_experts)
    with jax.named_scope("lm.moe.experts"):
        weight = jnp.where(held, w, 0.0).astype(x.dtype)
        kernels, routing = (p["w1"], p["w3"], p["w2"]), (order, inverse, held, group_sizes)
        out = _held_experts(x, weight, kernels, routing, rows_wide)
    rows = group_sizes.astype(jnp.float32)
    counters = {
        "pairs_here": jnp.sum(rows),
        "pairs_total": jnp.float32(n_rows * k),
        "load_max_over_mean": jnp.max(rows) / jnp.maximum(jnp.mean(rows), 1.0),
        "rows_per_expert_min": jnp.min(rows),
        "compact": (jnp.sum(rows) <= rows_wide).astype(jnp.float32),
    }
    return out, chosen, counters


# ------------------------------------------------------------------------ whole model
def _ffn_half(p, h, cfg: LMConfig, ffn: str):
    normed = rms_norm(h, p["ffn_norm"], cfg.norm_eps)
    if ffn == "dense":
        return h + gated_mlp(p["ffn"], normed), None
    flat, chosen, counters = moe_ffn(p["moe"], normed.reshape(-1, normed.shape[-1]), cfg)
    return h + flat.reshape(h.shape), (chosen, counters)


def _layer(p, x, cfg: LMConfig, mixer: str, ffn: str):
    """One block over whole sequences; returns the block's output and its aux. For its
    backward pass the block keeps its input and its matmul products (the arrays marked
    :data:`PRODUCT`: 4 x the input's bytes in a conv block, 7 x in the dense FFN at the
    published widths, 2.5 x in the attention block) and makes everything else again going
    backwards (``jax.checkpoint``): norms, gates, taps, activations and rotary are vector
    work over arrays the products define, and keeping them would cost gigabytes. The
    expert layer keeps its inputs only (:func:`_held_experts`). Around the flash kernel
    the block is two such halves, so that the kernel runs once forwards (it keeps what
    its own backward pass needs: q, k, v, the output and the row statistics, 0.2 GB at
    2 x 8,192 positions)."""
    block = functools.partial(jax.checkpoint, policy=KEEP_PRODUCTS)
    if mixer == "attn" and on_tpu():

        def qkv(p, x):
            with jax.named_scope("lm.attn"):
                return _qkv(p["attn"], rms_norm(x, p["op_norm"], cfg.norm_eps), cfg, jnp.arange(x.shape[1])[None, :])

        def rest(p, x, attended):
            with jax.named_scope("lm.attn"):
                h = x + checkpoint_name(attended @ p["attn"]["o"], PRODUCT)
            return _ffn_half(p, h, cfg, ffn)

        q, k, v = block(qkv)(p, x)
        with jax.named_scope("lm.attn"):
            attended = flash_core(q, k, v, cfg)
        return block(rest)(p, x, attended)

    def whole(p, x):
        normed = rms_norm(x, p["op_norm"], cfg.norm_eps)
        op = conv_op(p["conv"], normed)[0] if mixer == "conv" else attn_op(p["attn"], normed, cfg)[0]
        return _ffn_half(p, x + op, cfg, ffn)

    return block(whole)(p, x)


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LMConfig, dtype=jnp.float32):
    """``tokens`` [B, T] int, every sequence from position 0 -> the final normed
    state [B, T, D] in ``dtype`` and the aux: ``choices`` [n_moe, B*T, k] and the
    expert layers' counters, each [n_moe]."""
    p16 = working_copy(params, dtype)
    with jax.named_scope("lm.embed"):
        x = p16["embed"][tokens]
    choices, counters = [], []
    for n, (mixer, ffn) in enumerate(cfg.kinds):
        x, aux = _layer(p16["layers"][f"layer_{n}"], x, cfg, mixer, ffn)
        if aux is not None:
            choices.append(aux[0])
            counters.append(aux[1])
    final = rms_norm(x, p16["final_norm"], cfg.norm_eps)
    aux = {"embed": p16["embed"], "critic": p16["critic"]}
    if choices:
        aux["choices"] = jnp.stack(choices)
        aux["counters"] = {k: jnp.stack([c[k] for c in counters]) for k in counters[0]}
    return final, aux


def moe_metrics(aux: Dict[str, Any]) -> Dict[str, jax.Array]:
    """The expert layers' counters as the train call's metrics, and with them the routing itself
    (``Moe/choices`` [n_moe, tokens, k] int32, 1 MB at 16,384 tokens: the record a comparison with a
    reference needs, since near-ties of the top-k flip under any rounding). Nothing for a model
    without expert layers."""
    if "counters" not in aux:
        return {}
    c = aux["counters"]
    return {
        "Moe/choices": aux["choices"],
        "Moe/pairs_here": jnp.sum(c["pairs_here"]),
        "Moe/pairs_total": jnp.sum(c["pairs_total"]),
        "Moe/load_max_over_mean": jnp.max(c["load_max_over_mean"]),
        "Moe/rows_per_expert_min": jnp.min(c["rows_per_expert_min"]),
        "Moe/compact_share": jnp.mean(c["compact"]),
    }


def _head(final, embed, actions):
    """Log-prob of ``actions`` and entropy over the held vocabulary, float32: ``final`` [N, D]."""
    logits = jnp.matmul(final, embed.T, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    entropy = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    return jnp.take_along_axis(logp, actions[:, None], axis=-1)[:, 0], entropy


def evaluate(params: Dict[str, Any], tokens: jax.Array, actions: jax.Array, cfg: LMConfig, dtype=jnp.float32):
    """Teacher-forced pass over whole sequences: (log-prob of ``actions``, entropy,
    value), each [B, T] float32, and the aux of :func:`forward`. The logits exist
    ``head_chunk`` positions of one sequence at a time, forwards and backwards."""
    final, aux = forward(params, tokens, cfg, dtype)
    bsz, t, d = final.shape
    with jax.named_scope("lm.head"):
        chunk = min(cfg.head_chunk, t)
        if t % chunk:
            raise ValueError(f"a sequence of {t} positions is not a whole number of head chunks of {chunk}")
        embed = aux["embed"]
        head = jax.checkpoint(_head)
        logp, entropy = jax.lax.map(
            lambda fa: head(fa[0], embed, fa[1]),
            (final.reshape(-1, chunk, d), actions.reshape(-1, chunk)),
        )
        values = jnp.matmul(final, aux["critic"], preferred_element_type=jnp.float32)[..., 0]
    return logp.reshape(bsz, t), entropy.reshape(bsz, t), values, aux


def logits_and_values(params: Dict[str, Any], tokens: jax.Array, cfg: LMConfig, dtype=jnp.float32):
    """All logits [B, T, V] float32 and values [B, T] of whole sequences (small sizes: tests, evaluation)."""
    final, aux = forward(params, tokens, cfg, dtype)
    with jax.named_scope("lm.head"):
        logits = jnp.matmul(final, aux["embed"].T, preferred_element_type=jnp.float32)
        values = jnp.matmul(final, aux["critic"], preferred_element_type=jnp.float32)[..., 0]
    return logits, values


# ------------------------------------------------------------------------------ acting
def init_state(cfg: LMConfig, batch: int, dtype=jnp.float32) -> Dict[str, Any]:
    """The carried state of ``batch`` sequences at their start. Every leaf has the
    sequences on its first axis."""
    d, taps = cfg.hidden_size, cfg.conv_L_cache
    state: Dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32), "layers": {}}
    for n, (mixer, _) in enumerate(cfg.kinds):
        if mixer == "conv":
            state["layers"][f"layer_{n}"] = {"tail": jnp.zeros((batch, taps - 1, d), dtype)}
        else:
            kv = (batch, cfg.max_positions, cfg.num_key_value_heads, cfg.head)
            state["layers"][f"layer_{n}"] = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    return state


def reset_state(state: Dict[str, Any], keep: jax.Array) -> Dict[str, Any]:
    """The state with the sequences where ``keep`` [B] is 0 back at their start:
    position and convolution tails zeroed. Keys and values past the position are
    never attended to, so the caches stay as they are."""

    def one(path, leaf):
        if getattr(path[-1], "key", None) in ("k", "v"):
            return leaf
        k = keep.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return (leaf * k.astype(leaf.dtype)).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(one, state)


def decode_step(params: Dict[str, Any], tokens: jax.Array, state: Dict[str, Any], cfg: LMConfig, dtype=jnp.float32):
    """One token a sequence through the carried state: ``tokens`` [B] ->
    (logits [B, V] float32, values [B] float32, the new state)."""
    p16 = working_copy(params, dtype)
    pos = state["pos"]
    with jax.named_scope("lm.embed"):
        x = p16["embed"][tokens][:, None, :]
    new_layers = {}
    for n, (mixer, ffn) in enumerate(cfg.kinds):
        p, st = p16["layers"][f"layer_{n}"], state["layers"][f"layer_{n}"]
        normed = rms_norm(x, p["op_norm"], cfg.norm_eps)
        if mixer == "conv":
            op, tail = conv_op(p["conv"], normed, st["tail"])
            new_layers[f"layer_{n}"] = {"tail": tail.astype(st["tail"].dtype)}
        else:
            op, k, v = attn_decode(p["attn"], normed, cfg, st["k"], st["v"], pos)
            new_layers[f"layer_{n}"] = {"k": k, "v": v}
        h = x + op
        normed = rms_norm(h, p["ffn_norm"], cfg.norm_eps)
        if ffn == "dense":
            x = h + gated_mlp(p["ffn"], normed)
        else:
            flat, _, _ = moe_ffn(p["moe"], normed.reshape(-1, normed.shape[-1]), cfg)
            x = h + flat.reshape(h.shape)
    final = rms_norm(x, p16["final_norm"], cfg.norm_eps)[:, 0]
    with jax.named_scope("lm.head"):
        logits = jnp.matmul(final, p16["embed"].T, preferred_element_type=jnp.float32)
        values = jnp.matmul(final, p16["critic"], preferred_element_type=jnp.float32)[..., 0]
    return logits, values, {"pos": pos + 1, "layers": new_layers}
