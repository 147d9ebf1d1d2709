"""Fused RSSM dynamic-step kernel: LayerNorm-GRU + prior/posterior heads + ST sample.

One launch per ``lax.scan`` step fuses what the flax path runs as ~twenty XLA
ops: the input projection (``RecurrentModel``'s MLP + LayerNorm), the Hafner
LayerNorm-after-matmul GRU gate math (``models/models.py`` ``LayerNormGRUCell``),
both MLP-with-head trunks (transition -> prior logits, representation ->
posterior logits), the 1% uniform mixture, and the one-hot straight-through
posterior sample. The recurrent state and gate activations never round-trip HBM
between those stages.

Three implementations of the SAME math (``RSSMStepSpec.impl``):

- ``pallas``    — the TPU kernel (whole step in VMEM, one grid cell; gated by
  :func:`step_vmem_bytes`). Mosaic refuses it today (:data:`_MOSAIC_REFUSAL`):
  forcing it fails the compile with that message;
- ``interpret`` — the same kernel through the Pallas interpreter, runnable on
  CPU: the bit-parity harness (``tests/test_ops/test_pallas_rssm.py``);
- ``reference`` — the fused formulation as plain jnp (what ``auto`` uses).
  Identical op sequence, so interpret-vs-reference parity is bitwise.

The backward is a hand-written ``custom_vjp`` whose residuals are the step
*inputs only* (carries + scanned xs — arrays the scan materializes anyway);
every intermediate is recomputed in the backward. XLA autodiff of the flax step
instead stacks the gate/trunk/softmax intermediates per scan step
(``[T, B, ...]`` residual buffers — real HBM traffic that ``cost_analysis``
counts), which is where the bytes-accessed win measured by
``bench.py --target rssm`` comes from.

Precision policy (the f32 islands of ROADMAP item 3a): matmuls and gate
algebra run in the model compute dtype (bf16 under ``bf16-mixed``); LayerNorm
statistics, softmax / log-mixture math, and the logits handed to the KL loss
are pinned to f32. Under f32 compute every island cast is a no-op, so the
``kernels=off`` flax path stays the bitwise reference.

Straight-through sampling needs no ``stop_gradient`` inside the kernel: the
forward VALUE of ``rsample = sample + probs - sg(probs)`` is exactly the
one-hot sample (``probs - probs == 0``), and the probs path lives entirely in
the hand-written backward. ``jax.random.categorical(key, logits)`` is
``argmax(logits + gumbel)``, so the scan precomputes the Gumbel field
``[T, B, S, D]`` once and the kernel only does argmax + one-hot — the fused
path is distribution-equivalent (not bitwise) to the flax sampler; only
``kernels=off`` reproduces flax traces bit-for-bit.

Supersedes the removed single-op Pallas GRU (benchmarks/PALLAS_GRU_NOTES.md),
whose notes concluded only a whole-step fusion could beat XLA's own fusions.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

_logger = logging.getLogger("sheeprl_tpu.kernels")

__all__ = [
    "KernelUnsupported",
    "RSSMStepSpec",
    "extract_step_params",
    "fused_dynamic_scan",
    "fused_imagination_step",
    "log_choice_once",
    "select_impl",
    "step_vmem_bytes",
]


class KernelUnsupported(Exception):
    """The RSSM config/params don't match the fused-step contract. Under
    ``kernels=auto`` callers log it once and use the flax scan; under a named
    implementation it propagates."""


#: fixed parameter ordering — the pallas kernels take these positionally.
PARAM_KEYS = (
    "wi_z", "wi_a", "ln_i_scale", "ln_i_bias",
    "wg_h", "wg_f", "ln_g_scale", "ln_g_bias",
    "wt", "ln_t_scale", "ln_t_bias", "wt_head", "bt_head",
    "wr_h", "wr_e", "ln_r_scale", "ln_r_bias", "wr_head", "br_head",
)

#: VMEM budget for the single-grid-cell kernel: handed to Mosaic as
#: ``vmem_limit_bytes`` (its default scoped limit is ~16 MiB) and checked by the
#: dispatch gate; a forced ``pallas`` beyond it raises. NOT established on a
#: chip: Mosaic refuses the kernel before it allocates anything (PR 22).
_VMEM_BUDGET_ENV = "SHEEPRL_TPU_KERNEL_VMEM_BUDGET"
_VMEM_BUDGET_DEFAULT = 96 * 1024 * 1024

#: Why ``auto`` does not pick the Pallas kernel on a TPU: the compiler's own
#: words from the PR 22 chip run (TPU v5 lite, jax 0.9.0, libtpu 0.0.34) at the
#: DV3-S recipe shapes, after the API renames were repaired.
_MOSAIC_REFUSAL = (
    "Mosaic failed to compile TPU kernel: infer-vector-layout: unsupported shape cast "
    '("tpu.reshape"(...) : (vector<16x1024xf32>) -> vector<16x32x32xf32>)'
)


@dataclasses.dataclass(frozen=True)
class RSSMStepSpec:
    """Static description of one fused step (hashable: it rides custom_vjp's
    nondiff_argnums and jit static args)."""

    action_size: int
    embed_size: int
    dense_units: int      # RecurrentModel MLP width (GRU input projection)
    recurrent_size: int
    trans_hidden: int     # transition (prior) trunk width
    repr_hidden: int      # representation (posterior) trunk width
    stochastic: int
    discrete: int
    unimix: float
    eps_in: float         # input-projection LayerNorm epsilon
    eps_gru: float        # GRU fused-projection LayerNorm epsilon
    eps_trans: float
    eps_repr: float
    dtype: str = "float32"   # compute dtype name (params are always f32)
    impl: str = "reference"  # "pallas" | "interpret" | "reference"

    @property
    def stoch_flat(self) -> int:
        return self.stochastic * self.discrete

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def with_impl(self, impl: str) -> "RSSMStepSpec":
        return dataclasses.replace(self, impl=impl)


# --------------------------------------------------------------------------- #
# parameter extraction (flax trees -> flat dict the kernel understands)
# --------------------------------------------------------------------------- #


def _tree_get(tree: Any, *path: str) -> Any:
    node = tree
    for key in path:
        try:
            node = node[key]
        except (KeyError, TypeError, IndexError) as e:
            raise KernelUnsupported(
                f"missing parameter path {'/'.join(path)} (at {key!r}): {e}"
            ) from e
    return node


def extract_step_params(wm_params: Dict[str, Any], stoch_flat: int) -> Dict[str, jax.Array]:
    """Flatten the world-model param tree into the kernel's flat dict.

    Splits the fused input matrices at extraction time (``[z | a] @ Wi`` becomes
    ``z @ Wi_z + a @ Wi_a``) so the kernel never concatenates — the two partial
    matmuls hit the MXU directly and the backward splits fall out for free.
    Raises :class:`KernelUnsupported` on any structural mismatch (bias where the
    contract expects LayerNorm, missing LN params, extra MLP layers).
    """
    rec_mlp = _tree_get(wm_params, "recurrent_model", "params", "MLP_0")
    if "Dense_1" in rec_mlp:
        raise KernelUnsupported("recurrent projection must be a single Dense layer")
    rec_dense = _tree_get(rec_mlp, "Dense_0")
    if "bias" in rec_dense:
        raise KernelUnsupported("recurrent projection carries a bias (layer_norm off?)")
    wi = rec_dense["kernel"]
    ln_i = _tree_get(rec_mlp, "LayerNorm_0", "LayerNorm_0")
    gru = _tree_get(wm_params, "recurrent_model", "params", "LayerNormGRUCell_0")
    if "bias" in gru:
        raise KernelUnsupported("GRU cell carries a bias (hafner layer_norm variant expected)")
    if "ln_scale" not in gru or "ln_bias" not in gru:
        raise KernelUnsupported("GRU cell lacks LayerNorm parameters")
    wg = gru["kernel"]
    recurrent_size = wg.shape[-1] // 3

    def head(model_key: str) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
        mlp = _tree_get(wm_params, model_key, "params", "MLP_0")
        if "Dense_1" in mlp:
            raise KernelUnsupported(f"{model_key} trunk must be a single Dense layer")
        dense = _tree_get(mlp, "Dense_0")
        if "bias" in dense:
            raise KernelUnsupported(f"{model_key} trunk carries a bias (layer_norm off?)")
        ln = _tree_get(mlp, "LayerNorm_0", "LayerNorm_0")
        hd = _tree_get(wm_params, model_key, "params", "head")
        return dense["kernel"], ln["scale"], ln["bias"], hd["kernel"], hd["bias"]

    wt, ln_t_scale, ln_t_bias, wt_head, bt_head = head("transition_model")
    wr, ln_r_scale, ln_r_bias, wr_head, br_head = head("representation_model")

    if wi.shape[0] <= stoch_flat:
        raise KernelUnsupported(
            f"input projection rows {wi.shape[0]} cannot split at stoch size {stoch_flat}"
        )
    if wr.shape[0] <= recurrent_size:
        raise KernelUnsupported(
            f"representation rows {wr.shape[0]} cannot split at recurrent size {recurrent_size}"
        )
    return {
        "wi_z": wi[:stoch_flat], "wi_a": wi[stoch_flat:],
        "ln_i_scale": ln_i["scale"], "ln_i_bias": ln_i["bias"],
        "wg_h": wg[:recurrent_size], "wg_f": wg[recurrent_size:],
        "ln_g_scale": gru["ln_scale"], "ln_g_bias": gru["ln_bias"],
        "wt": wt, "ln_t_scale": ln_t_scale, "ln_t_bias": ln_t_bias,
        "wt_head": wt_head, "bt_head": bt_head,
        "wr_h": wr[:recurrent_size], "wr_e": wr[recurrent_size:],
        "ln_r_scale": ln_r_scale, "ln_r_bias": ln_r_bias,
        "wr_head": wr_head, "br_head": br_head,
    }


# --------------------------------------------------------------------------- #
# shared step math (runs as plain jnp AND inside the pallas kernels)
# --------------------------------------------------------------------------- #


def _mm(x_c: jax.Array, w_c: jax.Array) -> jax.Array:
    """Compute-dtype matmul with an explicit f32 accumulator, rounded back to
    the compute dtype. Mosaic rejects a bf16 accumulator ("Expected matmul acc
    to be 32-bit"); XLA's own TPU bf16 dot accumulates in f32 as well, so this
    is the same arithmetic spelled out. A no-op change under f32 compute."""
    return jnp.dot(x_c, w_c, preferred_element_type=jnp.float32).astype(x_c.dtype)


def _sigmoid(x_c: jax.Array) -> jax.Array:
    """Logistic through an f32 island: the Pallas TPU lowering of
    ``lax.logistic`` on bf16 fails verification in JAX 0.9.0 (an f32 scalar
    broadcast into a bf16 vector), and v5e has no bf16 VPU, so XLA widens the
    op the same way. A no-op change under f32 compute."""
    return jax.nn.sigmoid(x_c.astype(jnp.float32)).astype(x_c.dtype)


def _silu(x_c: jax.Array) -> jax.Array:
    return x_c * _sigmoid(x_c)


def _ln_f32(x_c: jax.Array, scale: jax.Array, bias: jax.Array, eps: float):
    """f32-island LayerNorm (stats in f32, like models.LayerNorm / the GRU cell).
    Returns (y32, xhat, inv) — xhat/inv feed the hand-written vjp."""
    x32 = x_c.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xc * inv
    return xhat * scale + bias, xhat, inv


def _ln_vjp(dy32, xhat, inv, scale, batch_axes):
    """Backward of :func:`_ln_f32` with biased variance over the last axis."""
    dscale = jnp.sum(dy32 * xhat, axis=batch_axes)
    dbias = jnp.sum(dy32, axis=batch_axes)
    dxhat = dy32 * scale
    dx32 = inv * (
        dxhat
        - jnp.mean(dxhat, axis=-1, keepdims=True)
        - xhat * jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    )
    return dx32, dscale, dbias


def _silu_grad(x):
    s = jax.nn.sigmoid(x)
    return s * (1.0 + x * (1.0 - s))


def _softmax_vjp(probs, dprobs):
    return probs * (dprobs - jnp.sum(probs * dprobs, axis=-1, keepdims=True))


def _unimix_logits(raw_c: jax.Array, spec: RSSMStepSpec):
    """f32-island uniform mixture: ``[B, S*D]`` raw head output -> ``[B, S, D]``
    log-mixture logits. Returns (logits32, pre-mix probs Q, mixed probs Qm)."""
    raw32 = raw_c.astype(jnp.float32).reshape(*raw_c.shape[:-1], spec.stochastic, spec.discrete)
    if spec.unimix > 0.0:
        q = jax.nn.softmax(raw32, axis=-1)
        qm = (1.0 - spec.unimix) * q + spec.unimix / spec.discrete
        return jnp.log(qm), q, qm
    # no mixture: logits pass through; normalized probs still feed the ST vjp
    q = jax.nn.softmax(raw32, axis=-1)
    return raw32, q, q


def _unimix_vjp(dlogits32, q, qm, spec: RSSMStepSpec):
    """Backward of :func:`_unimix_logits` down to the flat raw head output."""
    if spec.unimix > 0.0:
        dqm = dlogits32 / qm
        dq = (1.0 - spec.unimix) * dqm
        draw32 = _softmax_vjp(q, dq)
    else:
        draw32 = dlogits32
    return draw32.reshape(*draw32.shape[:-2], spec.stoch_flat)


def _st_onehot(logits32: jax.Array, gumbel: jax.Array, dtype) -> jax.Array:
    """Straight-through sample: ``argmax(logits + g)`` as a one-hot
    (``jax.random.categorical`` ≡ Gumbel-argmax), plus the zero-valued
    ``probs - stop_grad(probs)`` term that routes the softmax gradient through
    under autodiff — grouped so the forward value stays EXACTLY the one-hot
    (``x - x == 0`` elementwise; ``hard + probs - probs`` would re-round).
    2D+ iota keeps the TPU lowering legal (pallas guide: 1D iota does not
    vectorize)."""
    y = logits32 + gumbel
    idx = jnp.argmax(y, axis=-1)
    iota = jax.lax.broadcasted_iota(jnp.int32, y.shape, y.ndim - 1)
    hard = (iota == idx[..., None]).astype(logits32.dtype)
    probs = jax.nn.softmax(logits32, axis=-1)
    return (hard + (probs - jax.lax.stop_gradient(probs))).astype(dtype)


def _dyn_math(
    p: Dict[str, jax.Array],
    spec: RSSMStepSpec,
    init_h: jax.Array,   # [B, R]  (compute dtype)
    init_z: jax.Array,   # [B, S*D]
    h: jax.Array,        # [B, R] carry
    z: jax.Array,        # [B, S*D] carry
    a: jax.Array,        # [B, A]
    e: jax.Array,        # [B, E]
    f: jax.Array,        # [B, 1] is_first
    g: jax.Array,        # [B, S, D] gumbel field (f32)
    want_res: bool = False,
):
    """The whole fused step. Shared verbatim between the reference impl, the
    pallas kernel bodies, and the backward's recompute — one source of truth."""
    c = spec.compute_dtype
    f_c = f.astype(c)
    a_m = (1.0 - f_c) * a.astype(c)
    h0 = (1.0 - f_c) * h.astype(c) + f_c * init_h.astype(c)
    z0 = (1.0 - f_c) * z.astype(c) + f_c * init_z.astype(c)

    # input projection (RecurrentModel MLP, activation=None, no bias)
    t0 = _mm(z0, p["wi_z"].astype(c)) + _mm(a_m, p["wi_a"].astype(c))
    t_ln32, xhat1, inv1 = _ln_f32(t0, p["ln_i_scale"], p["ln_i_bias"], spec.eps_in)
    feat = t_ln32.astype(c)

    # Hafner GRU: fused projection -> f32 LN -> (reset, cand, update)
    u0 = _mm(h0, p["wg_h"].astype(c)) + _mm(feat, p["wg_f"].astype(c))
    g_ln32, xhat2, inv2 = _ln_f32(u0, p["ln_g_scale"], p["ln_g_bias"], spec.eps_gru)
    gates = g_ln32.astype(c)
    r_pre, c_pre, u_pre = jnp.split(gates, 3, axis=-1)
    r = _sigmoid(r_pre)
    cand = jnp.tanh(r * c_pre)
    u = _sigmoid(u_pre - 1.0)
    h_new = u * cand + (1.0 - u) * h0

    # prior head (transition): trunk -> f32 unimix logits
    pt0 = _mm(h_new, p["wt"].astype(c))
    p_ln32, xhat3, inv3 = _ln_f32(pt0, p["ln_t_scale"], p["ln_t_bias"], spec.eps_trans)
    p_ln = p_ln32.astype(c)
    pact = _silu(p_ln)
    prior_raw = _mm(pact, p["wt_head"].astype(c)) + p["bt_head"].astype(c)
    prior_logits, q_prior, qm_prior = _unimix_logits(prior_raw, spec)

    # posterior head (representation) + straight-through sample
    q0 = _mm(h_new, p["wr_h"].astype(c)) + _mm(e.astype(c), p["wr_e"].astype(c))
    q_ln32, xhat4, inv4 = _ln_f32(q0, p["ln_r_scale"], p["ln_r_bias"], spec.eps_repr)
    q_ln = q_ln32.astype(c)
    qact = _silu(q_ln)
    post_raw = _mm(qact, p["wr_head"].astype(c)) + p["br_head"].astype(c)
    post_logits, q_post, qm_post = _unimix_logits(post_raw, spec)
    z_new = _st_onehot(post_logits, g, c).reshape(h.shape[0], spec.stoch_flat)

    outs = (h_new, z_new, post_logits, prior_logits)
    if not want_res:
        return outs, None
    res = dict(
        f_c=f_c, a_m=a_m, h0=h0, z0=z0, feat=feat,
        xhat1=xhat1, inv1=inv1, xhat2=xhat2, inv2=inv2,
        r=r, c_pre=c_pre, cand=cand, u=u, h_new=h_new,
        p_ln=p_ln, pact=pact, xhat3=xhat3, inv3=inv3, q_prior=q_prior, qm_prior=qm_prior,
        q_ln=q_ln, qact=qact, xhat4=xhat4, inv4=inv4, q_post=q_post, qm_post=qm_post,
    )
    return outs, res


def _imag_math(
    p: Dict[str, jax.Array],
    spec: RSSMStepSpec,
    h: jax.Array,
    z: jax.Array,
    a: jax.Array,
    g: jax.Array,
    want_res: bool = False,
):
    """Imagination step: GRU + prior head + ST sample (no is_first gating, no
    representation branch — the actor interleaves between steps, so only the
    single step fuses, not the whole horizon scan)."""
    c = spec.compute_dtype
    t0 = _mm(z.astype(c), p["wi_z"].astype(c)) + _mm(a.astype(c), p["wi_a"].astype(c))
    t_ln32, xhat1, inv1 = _ln_f32(t0, p["ln_i_scale"], p["ln_i_bias"], spec.eps_in)
    feat = t_ln32.astype(c)
    h_c = h.astype(c)
    u0 = _mm(h_c, p["wg_h"].astype(c)) + _mm(feat, p["wg_f"].astype(c))
    g_ln32, xhat2, inv2 = _ln_f32(u0, p["ln_g_scale"], p["ln_g_bias"], spec.eps_gru)
    gates = g_ln32.astype(c)
    r_pre, c_pre, u_pre = jnp.split(gates, 3, axis=-1)
    r = _sigmoid(r_pre)
    cand = jnp.tanh(r * c_pre)
    u = _sigmoid(u_pre - 1.0)
    h_new = u * cand + (1.0 - u) * h_c
    pt0 = _mm(h_new, p["wt"].astype(c))
    p_ln32, xhat3, inv3 = _ln_f32(pt0, p["ln_t_scale"], p["ln_t_bias"], spec.eps_trans)
    p_ln = p_ln32.astype(c)
    pact = _silu(p_ln)
    prior_raw = _mm(pact, p["wt_head"].astype(c)) + p["bt_head"].astype(c)
    prior_logits, q_prior, qm_prior = _unimix_logits(prior_raw, spec)
    z_new = _st_onehot(prior_logits, g, c).reshape(h.shape[0], spec.stoch_flat)
    outs = (h_new, z_new)
    if not want_res:
        return outs, None
    res = dict(
        feat=feat, h_c=h_c, xhat1=xhat1, inv1=inv1, xhat2=xhat2, inv2=inv2,
        r=r, c_pre=c_pre, cand=cand, u=u, h_new=h_new,
        p_ln=p_ln, pact=pact, xhat3=xhat3, inv3=inv3, q_prior=q_prior, qm_prior=qm_prior,
    )
    return outs, res


# --------------------------------------------------------------------------- #
# pallas kernels (same math, refs in / refs out, whole step resident in VMEM)
# --------------------------------------------------------------------------- #


def _dyn_kernel(spec: RSSMStepSpec, *refs):
    n = len(PARAM_KEYS)
    p = {k: refs[i][...] for i, k in enumerate(PARAM_KEYS)}
    init_h, init_z, h, z, a, e, f, g = (r[...] for r in refs[n:n + 8])
    h_out, z_out, post_out, prior_out = refs[n + 8:]
    (h_new, z_new, post_logits, prior_logits), _ = _dyn_math(
        p, spec, init_h, init_z, h, z, a, e, f, g
    )
    h_out[...] = h_new
    z_out[...] = z_new
    post_out[...] = post_logits
    prior_out[...] = prior_logits


def _imag_kernel(spec: RSSMStepSpec, *refs):
    n = len(PARAM_KEYS)
    p = {k: refs[i][...] for i, k in enumerate(PARAM_KEYS)}
    h, z, a, g = (r[...] for r in refs[n:n + 4])
    h_out, z_out = refs[n + 4:]
    (h_new, z_new), _ = _imag_math(p, spec, h, z, a, g)
    h_out[...] = h_new
    z_out[...] = z_new


@functools.lru_cache(maxsize=None)
def _compiler_params(vmem_limit_bytes: int):
    """TPU compiler params, built lazily (the tpu submodule import is free on
    CPU but kept out of module import for belt-and-braces). The kernel is one
    grid cell with whole arrays resident, so Mosaic's default scoped-VMEM limit
    is raised to the budget the dispatch gate admitted the step under."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)


def _pallas_dyn_call(spec: RSSMStepSpec, p, init_h, init_z, h, z, a, e, f, g):
    from jax.experimental import pallas as pl

    b = h.shape[0]
    c = spec.compute_dtype
    out_shape = (
        jax.ShapeDtypeStruct((b, spec.recurrent_size), c),
        jax.ShapeDtypeStruct((b, spec.stoch_flat), c),
        jax.ShapeDtypeStruct((b, spec.stochastic, spec.discrete), jnp.float32),
        jax.ShapeDtypeStruct((b, spec.stochastic, spec.discrete), jnp.float32),
    )
    # string dispatch on the static spec (never a traced value): interpret mode
    # runs the kernel body through the Pallas interpreter and takes no TPU
    # compiler params
    kwargs: Dict[str, Any] = {"interpret": spec.impl == "interpret"}
    if spec.impl != "interpret":
        kwargs["compiler_params"] = _compiler_params(_vmem_budget())
    call = pl.pallas_call(
        functools.partial(_dyn_kernel, spec),
        out_shape=out_shape,
        **kwargs,
    )
    return call(*(p[k] for k in PARAM_KEYS), init_h, init_z, h, z, a, e, f, g)


def _pallas_imag_call(spec: RSSMStepSpec, p, h, z, a, g):
    from jax.experimental import pallas as pl

    b = h.shape[0]
    c = spec.compute_dtype
    out_shape = (
        jax.ShapeDtypeStruct((b, spec.recurrent_size), c),
        jax.ShapeDtypeStruct((b, spec.stoch_flat), c),
    )
    kwargs: Dict[str, Any] = {"interpret": spec.impl == "interpret"}
    if spec.impl != "interpret":
        kwargs["compiler_params"] = _compiler_params(_vmem_budget())
    call = pl.pallas_call(
        functools.partial(_imag_kernel, spec),
        out_shape=out_shape,
        **kwargs,
    )
    return call(*(p[k] for k in PARAM_KEYS), h, z, a, g)


# --------------------------------------------------------------------------- #
# custom_vjp: residuals = inputs, every intermediate recomputed in backward
# --------------------------------------------------------------------------- #


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_step(spec: RSSMStepSpec, p, init_h, init_z, h, z, a, e, f, g):
    if spec.impl in ("pallas", "interpret"):
        return _pallas_dyn_call(spec, p, init_h, init_z, h, z, a, e, f, g)
    outs, _ = _dyn_math(p, spec, init_h, init_z, h, z, a, e, f, g)
    return outs


def _fused_step_fwd(spec, p, init_h, init_z, h, z, a, e, f, g):
    outs = _fused_step(spec, p, init_h, init_z, h, z, a, e, f, g)
    # residuals: the step inputs, nothing else. The carries/xs are arrays the
    # scan already materializes; the params/init are loop-invariant (hoisted by
    # scan's partial-eval). This is the whole memory-traffic argument.
    return outs, (p, init_h, init_z, h, z, a, e, f, g)


def _matgrad(x_c, dout_c):
    """Parameter-gradient matmul in compute dtype, accumulated to the f32 param
    storage dtype (mirrors autodiff of ``x @ W.astype(c)``)."""
    return (x_c.T @ dout_c).astype(jnp.float32)


def _fused_step_bwd(spec, residuals, cts):
    p, init_h, init_z, h, z, a, e, f, g = residuals
    dh_out, dz_out, dpost_in, dprior_in = cts
    c = spec.compute_dtype
    _, R = _dyn_math(p, spec, init_h, init_z, h, z, a, e, f, g, want_res=True)

    # ---- straight-through sample: d(z_new)/d(probs) = I, probs = softmax(post_logits)
    dz32 = dz_out.reshape(*dpost_in.shape).astype(jnp.float32)
    dpost32 = dpost_in.astype(jnp.float32) + _softmax_vjp(R["qm_post"], dz32)
    dprior32 = dprior_in.astype(jnp.float32)

    # ---- posterior branch: unimix -> head -> silu -> LN -> split matmul
    dpost_raw = _unimix_vjp(dpost32, R["q_post"], R["qm_post"], spec).astype(c)
    dqact = dpost_raw @ p["wr_head"].astype(c).T
    dwr_head = _matgrad(R["qact"], dpost_raw)
    dbr_head = jnp.sum(dpost_raw, axis=0).astype(jnp.float32)
    dq_ln = dqact * _silu_grad(R["q_ln"])
    dq032, dln_r_scale, dln_r_bias = _ln_vjp(
        dq_ln.astype(jnp.float32), R["xhat4"], R["inv4"], p["ln_r_scale"], (0,)
    )
    dq0 = dq032.astype(c)
    e_c = e.astype(c)
    dh_new = dq0 @ p["wr_h"].astype(c).T
    dwr_h = _matgrad(R["h_new"], dq0)
    de = (dq0 @ p["wr_e"].astype(c).T).astype(e.dtype)
    dwr_e = _matgrad(e_c, dq0)

    # ---- prior branch
    dprior_raw = _unimix_vjp(dprior32, R["q_prior"], R["qm_prior"], spec).astype(c)
    dpact = dprior_raw @ p["wt_head"].astype(c).T
    dwt_head = _matgrad(R["pact"], dprior_raw)
    dbt_head = jnp.sum(dprior_raw, axis=0).astype(jnp.float32)
    dp_ln = dpact * _silu_grad(R["p_ln"])
    dpt032, dln_t_scale, dln_t_bias = _ln_vjp(
        dp_ln.astype(jnp.float32), R["xhat3"], R["inv3"], p["ln_t_scale"], (0,)
    )
    dpt0 = dpt032.astype(c)
    dh_new = dh_new + dpt0 @ p["wt"].astype(c).T
    dwt = _matgrad(R["h_new"], dpt0)

    # ---- GRU: total h_new cotangent = carry/output + both head branches
    dh_new = dh_new + dh_out.astype(c)
    u, cand, h0, r, c_pre = R["u"], R["cand"], R["h0"], R["r"], R["c_pre"]
    du = dh_new * (cand - h0)
    dcand = dh_new * u
    dh0 = dh_new * (1.0 - u)
    dct = dcand * (1.0 - cand * cand)
    dr = dct * c_pre
    dc_pre = dct * r
    dr_pre = dr * r * (1.0 - r)
    du_pre = du * u * (1.0 - u)
    dgates = jnp.concatenate([dr_pre, dc_pre, du_pre], axis=-1)
    du032, dln_g_scale, dln_g_bias = _ln_vjp(
        dgates.astype(jnp.float32), R["xhat2"], R["inv2"], p["ln_g_scale"], (0,)
    )
    du0 = du032.astype(c)
    dh0 = dh0 + du0 @ p["wg_h"].astype(c).T
    dwg_h = _matgrad(h0, du0)
    dfeat = du0 @ p["wg_f"].astype(c).T
    dwg_f = _matgrad(R["feat"], du0)

    # ---- input projection
    dt032, dln_i_scale, dln_i_bias = _ln_vjp(
        dfeat.astype(jnp.float32), R["xhat1"], R["inv1"], p["ln_i_scale"], (0,)
    )
    dt0 = dt032.astype(c)
    dz0 = dt0 @ p["wi_z"].astype(c).T
    dwi_z = _matgrad(R["z0"], dt0)
    da_m = dt0 @ p["wi_a"].astype(c).T
    dwi_a = _matgrad(R["a_m"], dt0)

    # ---- is_first gating (f and the gumbel field are data: zero cotangents)
    f_c = R["f_c"]
    dh_in = ((1.0 - f_c) * dh0).astype(h.dtype)
    dinit_h = (f_c * dh0).astype(init_h.dtype)
    dz_in = ((1.0 - f_c) * dz0).astype(z.dtype)
    dinit_z = (f_c * dz0).astype(init_z.dtype)
    da = ((1.0 - f_c) * da_m).astype(a.dtype)

    dp = {
        "wi_z": dwi_z, "wi_a": dwi_a, "ln_i_scale": dln_i_scale, "ln_i_bias": dln_i_bias,
        "wg_h": dwg_h, "wg_f": dwg_f, "ln_g_scale": dln_g_scale, "ln_g_bias": dln_g_bias,
        "wt": dwt, "ln_t_scale": dln_t_scale, "ln_t_bias": dln_t_bias,
        "wt_head": dwt_head, "bt_head": dbt_head,
        "wr_h": dwr_h, "wr_e": dwr_e, "ln_r_scale": dln_r_scale, "ln_r_bias": dln_r_bias,
        "wr_head": dwr_head, "br_head": dbr_head,
    }
    return (dp, dinit_h, dinit_z, dh_in, dz_in, da, de, jnp.zeros_like(f), jnp.zeros_like(g))


_fused_step.defvjp(_fused_step_fwd, _fused_step_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_imag_step(spec: RSSMStepSpec, p, h, z, a, g):
    if spec.impl in ("pallas", "interpret"):
        return _pallas_imag_call(spec, p, h, z, a, g)
    outs, _ = _imag_math(p, spec, h, z, a, g)
    return outs


def _fused_imag_step_fwd(spec, p, h, z, a, g):
    return _fused_imag_step(spec, p, h, z, a, g), (p, h, z, a, g)


def _fused_imag_step_bwd(spec, residuals, cts):
    p, h, z, a, g = residuals
    dh_out, dz_out = cts
    c = spec.compute_dtype
    _, R = _imag_math(p, spec, h, z, a, g, want_res=True)

    # straight-through prior sample -> prior logits -> head chain
    dz32 = dz_out.reshape(h.shape[0], spec.stochastic, spec.discrete).astype(jnp.float32)
    dprior32 = _softmax_vjp(R["qm_prior"], dz32)
    dprior_raw = _unimix_vjp(dprior32, R["q_prior"], R["qm_prior"], spec).astype(c)
    dpact = dprior_raw @ p["wt_head"].astype(c).T
    dwt_head = _matgrad(R["pact"], dprior_raw)
    dbt_head = jnp.sum(dprior_raw, axis=0).astype(jnp.float32)
    dp_ln = dpact * _silu_grad(R["p_ln"])
    dpt032, dln_t_scale, dln_t_bias = _ln_vjp(
        dp_ln.astype(jnp.float32), R["xhat3"], R["inv3"], p["ln_t_scale"], (0,)
    )
    dpt0 = dpt032.astype(c)
    dh_new = dpt0 @ p["wt"].astype(c).T + dh_out.astype(c)
    dwt = _matgrad(R["h_new"], dpt0)

    u, cand, h_c, r, c_pre = R["u"], R["cand"], R["h_c"], R["r"], R["c_pre"]
    du = dh_new * (cand - h_c)
    dcand = dh_new * u
    dh_c = dh_new * (1.0 - u)
    dct = dcand * (1.0 - cand * cand)
    dr = dct * c_pre
    dc_pre = dct * r
    dr_pre = dr * r * (1.0 - r)
    du_pre = du * u * (1.0 - u)
    dgates = jnp.concatenate([dr_pre, dc_pre, du_pre], axis=-1)
    du032, dln_g_scale, dln_g_bias = _ln_vjp(
        dgates.astype(jnp.float32), R["xhat2"], R["inv2"], p["ln_g_scale"], (0,)
    )
    du0 = du032.astype(c)
    dh_c = dh_c + du0 @ p["wg_h"].astype(c).T
    dwg_h = _matgrad(h_c, du0)
    dfeat = du0 @ p["wg_f"].astype(c).T
    dwg_f = _matgrad(R["feat"], du0)
    dt032, dln_i_scale, dln_i_bias = _ln_vjp(
        dfeat.astype(jnp.float32), R["xhat1"], R["inv1"], p["ln_i_scale"], (0,)
    )
    dt0 = dt032.astype(c)
    dz = (dt0 @ p["wi_z"].astype(c).T).astype(z.dtype)
    dwi_z = _matgrad(z.astype(c), dt0)
    da = (dt0 @ p["wi_a"].astype(c).T).astype(a.dtype)
    dwi_a = _matgrad(a.astype(c), dt0)

    zero32 = lambda k: jnp.zeros_like(p[k])  # noqa: E731 — untouched branch params
    dp = {
        "wi_z": dwi_z, "wi_a": dwi_a, "ln_i_scale": dln_i_scale, "ln_i_bias": dln_i_bias,
        "wg_h": dwg_h, "wg_f": dwg_f, "ln_g_scale": dln_g_scale, "ln_g_bias": dln_g_bias,
        "wt": dwt, "ln_t_scale": dln_t_scale, "ln_t_bias": dln_t_bias,
        "wt_head": dwt_head, "bt_head": dbt_head,
        "wr_h": zero32("wr_h"), "wr_e": zero32("wr_e"),
        "ln_r_scale": zero32("ln_r_scale"), "ln_r_bias": zero32("ln_r_bias"),
        "wr_head": zero32("wr_head"), "br_head": zero32("br_head"),
    }
    return (dp, (dh_c * (1.0 - 0.0)).astype(h.dtype), dz, da, jnp.zeros_like(g))


_fused_imag_step.defvjp(_fused_imag_step_fwd, _fused_imag_step_bwd)


# --------------------------------------------------------------------------- #
# scan-level entry points
# --------------------------------------------------------------------------- #


def initial_step_states(
    p: Dict[str, jax.Array],
    spec: RSSMStepSpec,
    init_raw: jax.Array,
    batch: int,
    learnable: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Hoisted ``RSSM.initial_states``: the flax step recomputes the learnable
    reset state (tanh + transition mode) EVERY scan step; the fused path
    computes it once and lets the scan accumulate its cotangent. The prior mode
    path (one_hot(argmax)) carries no gradient in either formulation."""
    c = spec.compute_dtype
    if not learnable:
        init_raw = jax.lax.stop_gradient(init_raw)
    init_row = jnp.tanh(init_raw).astype(c).reshape(-1)
    init_h = jnp.broadcast_to(init_row, (batch, spec.recurrent_size))
    pt0 = init_h @ p["wt"].astype(c)
    p_ln32, _, _ = _ln_f32(pt0, p["ln_t_scale"], p["ln_t_bias"], spec.eps_trans)
    pact = jax.nn.silu(p_ln32.astype(c))
    raw = pact @ p["wt_head"].astype(c) + p["bt_head"].astype(c)
    logits, _, _ = _unimix_logits(raw, spec)
    idx = jnp.argmax(logits, axis=-1)
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    init_z = (iota == idx[..., None]).astype(c).reshape(batch, spec.stoch_flat)
    return init_h, jax.lax.stop_gradient(init_z)


def fused_dynamic_scan(
    p: Dict[str, jax.Array],
    spec: RSSMStepSpec,
    init_raw: jax.Array,
    embedded_obs: jax.Array,   # [T, B, E]
    actions: jax.Array,        # [T, B, A]
    is_first: jax.Array,       # [T, B, 1]
    key: jax.Array,
    learnable_init: bool = True,
    unroll: int = 1,
    use_custom_vjp: bool = True,
):
    """Fused replacement for ``RSSM.dynamic_scan`` (non-decoupled path).

    Returns the flax contract: ``(recurrent_states [T,B,R], posteriors
    [T,B,S,D], priors_logits [T,B,S,D], posteriors_logits [T,B,S,D])`` — logits
    in f32 (the KL island), states/samples in the compute dtype.
    ``use_custom_vjp=False`` exposes the identical formulation to XLA autodiff:
    the gradient-parity baseline in the kernel test suite.
    """
    T, B = embedded_obs.shape[0], embedded_obs.shape[1]
    c = spec.compute_dtype
    init_h, init_z = initial_step_states(p, spec, init_raw, B, learnable=learnable_init)
    # Gumbel-argmax == jax.random.categorical: one [T,B,S,D] field drawn up
    # front replaces T in-scan sampler calls (distribution-equivalent to the
    # flax per-step keys, not bitwise — kernels=off is the bitwise reference).
    gumbel = jax.random.gumbel(
        jax.random.fold_in(key, 1), (T, B, spec.stochastic, spec.discrete), jnp.float32
    )
    carry0 = (jnp.zeros((B, spec.recurrent_size), c), jnp.zeros((B, spec.stoch_flat), c))

    def body(carry, xs):
        h, z = carry
        a, e, f, g = xs
        if use_custom_vjp:
            h1, z1, post_l, prior_l = _fused_step(spec, p, init_h, init_z, h, z, a, e, f, g)
        else:
            (h1, z1, post_l, prior_l), _ = _dyn_math(p, spec, init_h, init_z, h, z, a, e, f, g)
        ys = (h1, z1.reshape(B, spec.stochastic, spec.discrete), post_l, prior_l)
        return (h1, z1), ys

    _, (recurrent_states, posteriors, posteriors_logits, priors_logits) = jax.lax.scan(
        body, carry0, (actions, embedded_obs, is_first, gumbel), unroll=max(1, int(unroll))
    )
    return recurrent_states, posteriors, priors_logits, posteriors_logits


def fused_imagination_step(
    p: Dict[str, jax.Array],
    spec: RSSMStepSpec,
    prior_flat: jax.Array,
    recurrent_state: jax.Array,
    actions: jax.Array,
    key: jax.Array,
):
    """Fused replacement for ``RSSM.imagination_step``: returns
    ``(imagined_prior [B,S*D], recurrent_state [B,R])`` like the flax path
    (which reshapes the sample back to ``prior_flat.shape``)."""
    B = recurrent_state.shape[0]
    gumbel = jax.random.gumbel(key, (B, spec.stochastic, spec.discrete), jnp.float32)
    h_new, z_new = _fused_imag_step(spec, p, recurrent_state, prior_flat, actions, gumbel)
    return z_new.reshape(prior_flat.shape), h_new


# --------------------------------------------------------------------------- #
# dispatch: platform + VMEM gate + the kernel_dispatch failpoint
# --------------------------------------------------------------------------- #


def step_vmem_bytes(spec: RSSMStepSpec, batch: int) -> int:
    """Upper-bound VMEM footprint of one fused dynamic step: every parameter as
    stored (always f32) plus its compute-dtype cast, plus the activation set,
    resident at once (the kernel is a single grid cell — that's the fusion's
    whole point)."""
    c_bytes = jnp.dtype(spec.dtype).itemsize
    sd = spec.stoch_flat
    param_elems = (
        (sd + spec.action_size) * spec.dense_units + 2 * spec.dense_units
        + (spec.recurrent_size + spec.dense_units) * 3 * spec.recurrent_size
        + 2 * 3 * spec.recurrent_size
        + spec.recurrent_size * spec.trans_hidden + 2 * spec.trans_hidden
        + spec.trans_hidden * sd + sd
        + (spec.recurrent_size + spec.embed_size) * spec.repr_hidden + 2 * spec.repr_hidden
        + spec.repr_hidden * sd + sd
    )
    act_elems = batch * (
        sd * 4                       # z carry, z0, sample, gumbel/logits rows
        + spec.action_size
        + spec.embed_size
        + spec.recurrent_size * 2    # h carry + h_new
        + spec.dense_units * 2       # t0 + feat
        + 3 * spec.recurrent_size * 2  # fused gates (pre/post LN)
        + spec.trans_hidden * 2
        + spec.repr_hidden * 2
        + 2 * sd                     # both logits
    )
    param_bytes = 4 + (c_bytes if c_bytes != 4 else 0)  # f32 storage + the cast copy
    # LN statistics and the f32 islands run at 4 bytes regardless of c
    return param_elems * param_bytes + act_elems * max(c_bytes, 4)


def _vmem_budget() -> int:
    return int(os.environ.get(_VMEM_BUDGET_ENV, _VMEM_BUDGET_DEFAULT))


@functools.lru_cache(maxsize=None)
def log_choice_once(kernels: str, impl: str, why: str) -> None:
    _logger.warning("[kernels] world_model.kernels=%s -> %s: %s", kernels, impl, why)


def select_impl(
    kernels: str,
    spec: RSSMStepSpec,
    batch: int,
    platform: Optional[str] = None,
) -> Optional[str]:
    """Resolve the ``world_model.kernels`` knob to an implementation, or None
    for the flax scan.

    ``off`` -> None. A NAMED implementation (``pallas``/``interpret``/
    ``reference``) is what runs or the call raises: a forced ``pallas`` step
    beyond the VMEM budget is a ``ValueError``, and a kernel the compiler
    refuses fails the train step's compile with the compiler's own message —
    never another implementation in silence. ``auto`` picks the fused
    ``reference`` formulation (same math + custom_vjp, plain XLA — still
    removes the autodiff residual traffic) and logs once why: off TPU there is
    no Mosaic, and on TPU Mosaic refuses the kernel (:data:`_MOSAIC_REFUSAL`;
    ROADMAP Speed 5 judges whether it is redesigned or deleted). The
    ``train.kernel_dispatch`` failpoint forces the flax scan — the degradation
    drill for SA005-registered chaos runs.
    """
    kernels = str(kernels).lower()
    if kernels in ("off", "false", "0", "none"):
        return None
    if kernels not in ("auto", "on", "pallas", "interpret", "reference"):
        raise ValueError(
            f"world_model.kernels must be off/auto/pallas/interpret/reference, got {kernels!r}"
        )
    from sheeprl_tpu.core import failpoints

    if failpoints.failpoint("train.kernel_dispatch", requested=kernels, batch=batch):
        return None
    if kernels == "auto":
        platform = platform or jax.default_backend()
        why = (
            f"the compiler refuses the Pallas kernel ({_MOSAIC_REFUSAL})"
            if platform == "tpu"
            else f"no Mosaic on platform '{platform}'"
        )
        log_choice_once(kernels, "reference", why)
        return "reference"
    if kernels in ("on", "pallas"):
        need, budget = step_vmem_bytes(spec, batch), _vmem_budget()
        if need > budget:
            raise ValueError(
                f"world_model.kernels={kernels}: the fused step needs ~{need / 2**20:.1f} MiB of "
                f"VMEM at batch {batch}, over the {budget / 2**20:.1f} MiB budget "
                f"(${_VMEM_BUDGET_ENV}); use kernels=reference or kernels=off"
            )
        return "pallas"
    return kernels
