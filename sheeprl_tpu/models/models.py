"""Composable model library (flax.linen).

Functional parity with reference sheeprl/models/models.py — MLP (:16), CNN (:122),
DeCNN (:205), NatureCNN (:288), LayerNormGRUCell (:331, Hafner GRU: LayerNorm after
input projection, update-gate bias -1), MultiEncoder (:413), MultiDecoder (:478),
LayerNormChannelLast (:507), LayerNorm (:521) — re-designed for TPU:

- convs run in NHWC internally (XLA:TPU's preferred layout for the MXU); the public
  API keeps the reference's CHW tensors, transposes are fused by XLA;
- precision policy via ``dtype``/``param_dtype`` fields (params fp32, compute bf16 in
  'bf16-mixed'); LayerNorms compute in fp32 and cast back (dtype-preserving, like the
  reference's LayerNorm :521-525);
- activation/normalization selected by name (configs carry strings, not classes).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax import traverse_util
from jax import lax

ModuleType = Any
Dtype = Any

_ACTIVATIONS: Dict[str, Callable] = {
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
    "elu": jax.nn.elu,
    "gelu": jax.nn.gelu,
    "leaky_relu": jax.nn.leaky_relu,
    "sigmoid": jax.nn.sigmoid,
    "identity": lambda x: x,
    "none": lambda x: x,
}


def get_activation(name: Optional[Union[str, Callable]]) -> Callable:
    if name is None:
        return lambda x: x
    if callable(name):
        return name
    key = str(name).rsplit(".", 1)[-1].lower()  # accept "torch.nn.SiLU"-style strings
    if key not in _ACTIVATIONS:
        raise ValueError(f"Unknown activation '{name}'. Available: {sorted(_ACTIVATIONS)}")
    return _ACTIVATIONS[key]


def _per_layer(spec, n: int) -> Sequence:
    """Broadcast a possibly-scalar spec to one entry per layer (reference
    create_layers, sheeprl/utils/model.py:91)."""
    if isinstance(spec, (list, tuple)):
        if len(spec) != n:
            raise ValueError(f"Per-layer spec length {len(spec)} != number of layers {n}")
        return list(spec)
    return [spec] * n


def orthogonal_init(scale: float = 2**0.5):
    return nn.initializers.orthogonal(scale)


# ---- Weight gradients of a scanned step, taken outside the scan -------------
#
# A ``lax.scan`` whose step closes over a kernel sums that kernel's gradient in
# the backward scan's carry: every step reads and writes the whole float32
# kernel to add a product of rank B. `TapDot` and `kernel_taps` are the two
# halves of the way around it. Inside the step the kernel enters the product
# under ``stop_gradient``, so the scan has nothing to carry for it, and the
# product's input and the cotangent at its output leave the backward scan as the
# "cotangent" of a per-step tap (the cotangent of a scanned input is stacked,
# never summed). Outside, `kernel_taps` made those taps from the kernels, and its
# backward contracts the two stacks over every leading axis: one matmul.

TAPS = "taps"  # the flax collection a scan step passes beside ``params``


@jax.custom_vjp
def _tapped_matmul(x: jax.Array, w: jax.Array, tap: Tuple[jax.Array, jax.Array]) -> jax.Array:
    return lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())))


def _tapped_matmul_fwd(x, w, tap):
    return _tapped_matmul(x, w, tap), (x, w)


def _tapped_matmul_bwd(res, dy):
    x, w = res
    dx = lax.dot_general(dy, w, (((dy.ndim - 1,), (1,)), ((), ())))
    # the tap's "cotangent" is the pair `_kernel_taps_bwd` multiplies; ``w``
    # came in under stop_gradient, so its zeros are dropped, never summed
    return dx, jnp.zeros_like(w), (x, dy)


_tapped_matmul.defvjp(_tapped_matmul_fwd, _tapped_matmul_bwd)


class TapDot(nn.Module):
    """``dot_general_cls`` of a dense layer whose kernel a scan's step closes over.

    It is ``lax.dot_general`` unless the caller's variables hold a tap for it
    (`kernel_taps`): then the kernel's gradient does not flow through the
    product but through the tap, as the stacked pair (input, output cotangent).
    One tap serves ONE product: a module applied twice with the same tap would
    have its two inputs and its two cotangents added before they are multiplied.
    """

    def __call__(self, lhs, rhs, dimension_numbers, precision=None, preferred_element_type=None):
        if not self.has_variable(TAPS, "x"):
            return lax.dot_general(
                lhs, rhs, dimension_numbers, precision=precision, preferred_element_type=preferred_element_type
            )
        if dimension_numbers != (((lhs.ndim - 1,), (0,)), ((), ())) or precision is not None:
            raise ValueError(f"a tapped product is a plain `x @ w`, got {dimension_numbers}, precision={precision}")
        tap = (self.get_variable(TAPS, "x"), self.get_variable(TAPS, "y"))
        return _tapped_matmul(lhs, lax.stop_gradient(rhs), tap)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _kernel_taps(kernels: Dict[Tuple[str, ...], jax.Array], lead_shape: Tuple[int, ...], dtype: Any):
    return {
        path: (jnp.zeros((*lead_shape, w.shape[0]), dtype), jnp.zeros((*lead_shape, w.shape[1]), dtype))
        for path, w in kernels.items()
    }


def _kernel_taps_fwd(kernels, lead_shape, dtype):
    return _kernel_taps(kernels, lead_shape, dtype), kernels


def _kernel_taps_bwd(lead_shape, dtype, kernels, cts):
    lead = tuple(range(len(lead_shape)))
    return (
        {
            # the operands are the ones a per-step product has (compute dtype);
            # the sum over every leading axis is in float32 and rounded once
            path: lax.dot_general(*cts[path], ((lead, lead), ((), ())), preferred_element_type=jnp.float32).astype(
                w.dtype
            )
            for path, w in kernels.items()
        },
    )


_kernel_taps.defvjp(_kernel_taps_fwd, _kernel_taps_bwd)


def kernel_taps(params: Dict[str, Any], lead_shape: Sequence[int], dtype: Any) -> Dict[str, Any]:
    """The `TAPS` collection for the `TapDot` of every dense kernel in ``params``.

    Zeros of shape ``[*lead_shape, in]`` and ``[*lead_shape, out]`` in the
    compute ``dtype``, with ``lead_shape = (T, B)`` for a scan of T steps at
    batch B: scan over them, and pass each step's slice to ``apply`` beside
    ``params``. Their values are never read. Their cotangent is, by this
    function's backward, each kernel's gradient: ``einsum("tbk,tbn->kn")`` of
    what the products sent back. A kernel whose product is no `TapDot` keeps its
    ordinary gradient, and a tap nothing uses adds zero to it.
    """
    kernels = {
        path[:-1]: w
        for path, w in traverse_util.flatten_dict(params).items()
        if path[-1] == "kernel" and w.ndim == 2
    }
    taps = _kernel_taps(kernels, tuple(lead_shape), jnp.dtype(dtype))
    return traverse_util.unflatten_dict(
        {(*path, f"{TapDot.__name__}_0", name): tap for path, xy in taps.items() for name, tap in zip("xy", xy)}
    )


class LayerNorm(nn.Module):
    """fp32-computing, dtype-preserving LayerNorm (reference models.py:521-525)."""

    eps: float = 1e-5
    use_scale: bool = True
    use_bias: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        input_dtype = x.dtype
        out = nn.LayerNorm(epsilon=self.eps, use_scale=self.use_scale, use_bias=self.use_bias, dtype=jnp.float32)(
            x.astype(jnp.float32)
        )
        return out.astype(input_dtype)


class LayerNormChannelLast(nn.Module):
    """LayerNorm over the channel axis of an NCHW tensor (reference models.py:507-518).

    Internally permutes to channel-last (free on TPU: layout assignment), normalizes,
    and permutes back, preserving dtype.
    """

    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if x.ndim != 4:
            raise ValueError(f"Input tensor must be 4D (NCHW), received {x.ndim}D instead: {x.shape}")
        x = jnp.transpose(x, (0, 2, 3, 1))
        x = LayerNorm(eps=self.eps)(x)
        return jnp.transpose(x, (0, 3, 1, 2))


class MLP(nn.Module):
    """MLP backbone (reference models.py:16-119).

    Per-layer dropout -> normalization -> activation, with an optional final linear
    head (``output_dim``) and optional input flattening from ``flatten_dim``.
    ``use_bias`` applies to the hidden layers only (like the reference's
    ``layer_args``); the output head always has a bias, matching the reference's
    plain ``nn.Linear`` head.
    """

    input_dims: Union[int, Sequence[int]]
    output_dim: Optional[int] = None
    hidden_sizes: Sequence[int] = ()
    activation: Union[str, Sequence[str], Callable, None] = "relu"
    layer_norm: Union[bool, Sequence[bool]] = False
    norm_args: Optional[Union[Dict[str, Any], Sequence[Dict[str, Any]]]] = None
    dropout_rate: Union[float, Sequence[float], None] = None
    flatten_dim: Optional[int] = None
    use_bias: Union[bool, Sequence[bool]] = True
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    kernel_init: Optional[Callable] = None
    bias_init: Callable = nn.initializers.zeros_init()
    dot_general_cls: Any = None  # handed to every ``nn.Dense`` (`TapDot` for a scanned MLP)

    @property
    def out_features(self) -> int:
        if self.output_dim is not None:
            return self.output_dim
        if len(self.hidden_sizes) == 0:
            raise ValueError("The number of layers should be at least 1.")
        return self.hidden_sizes[-1]

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        n = len(self.hidden_sizes)
        if n < 1 and self.output_dim is None:
            raise ValueError("The number of layers should be at least 1.")
        if self.flatten_dim is not None:
            x = jnp.reshape(x, x.shape[: self.flatten_dim] + (-1,))
        x = x.astype(self.dtype)
        acts = _per_layer(self.activation, n)
        norms = _per_layer(self.layer_norm, n)
        norm_args = _per_layer(self.norm_args, n)
        drops = _per_layer(self.dropout_rate, n)
        biases = _per_layer(self.use_bias, n)
        kernel_init = self.kernel_init or nn.initializers.lecun_normal()
        for i, size in enumerate(self.hidden_sizes):
            x = nn.Dense(
                size,
                use_bias=biases[i],
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=kernel_init,
                bias_init=self.bias_init,
                dot_general_cls=self.dot_general_cls,
            )(x)
            if drops[i]:
                x = nn.Dropout(rate=drops[i])(x, deterministic=deterministic)
            if norms[i]:
                x = LayerNorm(**(norm_args[i] or {}))(x)
            x = get_activation(acts[i])(x)
        if self.output_dim is not None:
            x = nn.Dense(
                self.output_dim,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=kernel_init,
                bias_init=self.bias_init,
                dot_general_cls=self.dot_general_cls,
            )(x)
        return x


class CNN(nn.Module):
    """Conv stack (reference models.py:122-202). Input NCHW; compute NHWC on the MXU.

    ``layer_args`` carries per-layer ``kernel_size``/``stride``/``padding`` dicts
    (torch-style ints accepted).
    """

    input_channels: int
    hidden_channels: Sequence[int]
    layer_args: Optional[Union[Dict[str, Any], Sequence[Dict[str, Any]]]] = None
    activation: Union[str, Sequence[str], Callable, None] = "relu"
    layer_norm: Union[bool, Sequence[bool]] = False
    norm_args: Optional[Union[Dict[str, Any], Sequence[Dict[str, Any]]]] = None
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    kernel_init: Optional[Callable] = None

    @staticmethod
    def _conv_kwargs(args: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        args = dict(args or {})
        k = args.get("kernel_size", 3)
        s = args.get("stride", 1)
        p = args.get("padding", 0)
        kernel = (k, k) if isinstance(k, int) else tuple(k)
        strides = (s, s) if isinstance(s, int) else tuple(s)
        if isinstance(p, str):
            padding = p.upper()
        elif isinstance(p, int):
            padding = [(p, p), (p, p)]
        else:
            padding = [tuple(pp) if isinstance(pp, (list, tuple)) else (pp, pp) for pp in p]
        return {"kernel_size": kernel, "strides": strides, "padding": padding, "use_bias": args.get("bias", True)}

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        n = len(self.hidden_channels)
        acts = _per_layer(self.activation, n)
        norms = _per_layer(self.layer_norm, n)
        norm_args = _per_layer(self.norm_args, n)
        largs = _per_layer(self.layer_args, n)
        x = jnp.transpose(x.astype(self.dtype), (0, 2, 3, 1))  # NCHW -> NHWC
        for i, ch in enumerate(self.hidden_channels):
            x = nn.Conv(
                ch,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=self.kernel_init or nn.linear.default_kernel_init,
                **self._conv_kwargs(largs[i]),
            )(x)
            if norms[i]:
                x = LayerNorm(**(norm_args[i] or {}))(x)  # channel-last already
            x = get_activation(acts[i])(x)
        return jnp.transpose(x, (0, 3, 1, 2))  # back to NCHW


class DeCNN(nn.Module):
    """Transposed-conv stack (reference models.py:205-285). Input/output NCHW."""

    input_channels: int
    hidden_channels: Sequence[int]
    layer_args: Optional[Union[Dict[str, Any], Sequence[Dict[str, Any]]]] = None
    activation: Union[str, Sequence[str], Callable, None] = "relu"
    layer_norm: Union[bool, Sequence[bool]] = False
    norm_args: Optional[Union[Dict[str, Any], Sequence[Dict[str, Any]]]] = None
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    kernel_init: Optional[Union[Callable, Sequence[Optional[Callable]]]] = None

    @staticmethod
    def _deconv_kwargs(args: Optional[Dict[str, Any]]) -> Tuple[Dict[str, Any], int]:
        args = dict(args or {})
        k = args.get("kernel_size", 3)
        s = args.get("stride", 1)
        p = args.get("padding", 0)
        op = args.get("output_padding", 0)
        kernel = (k, k) if isinstance(k, int) else tuple(k)
        strides = (s, s) if isinstance(s, int) else tuple(s)
        pad = p if isinstance(p, int) else p[0]
        return (
            {"kernel_size": kernel, "strides": strides, "use_bias": args.get("bias", True)},
            (pad, op),
        )

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        n = len(self.hidden_channels)
        acts = _per_layer(self.activation, n)
        norms = _per_layer(self.layer_norm, n)
        norm_args = _per_layer(self.norm_args, n)
        largs = _per_layer(self.layer_args, n)
        x = jnp.transpose(x.astype(self.dtype), (0, 2, 3, 1))
        for i, ch in enumerate(self.hidden_channels):
            kwargs, (pad, out_pad) = self._deconv_kwargs(largs[i])
            # torch ConvTranspose2d semantics: out = (in-1)*s - 2p + k + out_pad.
            # flax ConvTranspose with padding=[(k-1-p, k-1-p+out_pad)] matches.
            kh, _ = kwargs["kernel_size"]
            lo = kh - 1 - pad
            ki = self.kernel_init
            if isinstance(ki, (list, tuple)):
                ki = ki[i]
            x = nn.ConvTranspose(
                ch,
                padding=[(lo, lo + out_pad), (lo, lo + out_pad)],
                transpose_kernel=True,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=ki or nn.linear.default_kernel_init,
                **kwargs,
            )(x)
            if norms[i]:
                x = LayerNorm(**(norm_args[i] or {}))(x)
            x = get_activation(acts[i])(x)
        return jnp.transpose(x, (0, 3, 1, 2))


def cnn_forward(module, params, x: jax.Array, input_dim: Sequence[int], output_dim: Sequence[int], **kwargs):
    """Batch-flattening conv apply (reference sheeprl/utils/model.py:165-223).

    Flattens all leading dims to one batch axis, applies the module, restores them.
    """
    batch_shape = x.shape[: -len(input_dim)]
    flat = jnp.reshape(x, (-1, *input_dim))
    out = module.apply(params, flat, **kwargs) if params is not None else module(flat)
    return jnp.reshape(out, (*batch_shape, *output_dim))


class NatureCNN(nn.Module):
    """DQN-Nature encoder + linear head (reference models.py:288-328)."""

    in_channels: int
    features_dim: Optional[int] = 512
    screen_size: int = 64
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        backbone = CNN(
            input_channels=self.in_channels,
            hidden_channels=[32, 64, 64],
            layer_args=[
                {"kernel_size": 8, "stride": 4},
                {"kernel_size": 4, "stride": 2},
                {"kernel_size": 3, "stride": 1},
            ],
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        batch_shape = x.shape[:-3]
        flat = jnp.reshape(x, (-1, *x.shape[-3:]))
        feats = backbone(flat)
        feats = jnp.reshape(feats, (feats.shape[0], -1))
        if self.features_dim is not None:
            feats = nn.Dense(self.features_dim, dtype=self.dtype, param_dtype=self.param_dtype)(feats)
            feats = jax.nn.relu(feats)
        return jnp.reshape(feats, (*batch_shape, feats.shape[-1]))


class LayerNormGRUCell(nn.Module):
    """Hafner-variant GRU cell (reference models.py:331-410).

    One fused linear over ``concat(h, x)`` -> LayerNorm -> split into
    (reset, cand, update); ``update`` gate gets a -1 bias so the cell starts biased
    toward keeping state. The fused projection is a single MXU matmul per step, which
    is what makes the `lax.scan`-ed RSSM fast on TPU.
    """

    hidden_size: int
    bias: bool = True
    layer_norm: bool = False
    layer_norm_eps: float = 1e-5
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32
    kernel_init: Optional[Callable] = None
    dot_general_cls: Any = None  # as ``nn.Dense``'s (`TapDot` for a scanned cell)

    @nn.compact
    def __call__(self, x: jax.Array, h: jax.Array) -> jax.Array:
        n = 3 * self.hidden_size
        in_features = h.shape[-1] + x.shape[-1]
        kernel = self.param(
            "kernel",
            self.kernel_init or nn.linear.default_kernel_init,
            (in_features, n),
            self.param_dtype,
        )
        bias = self.param("bias", nn.initializers.zeros_init(), (n,), self.param_dtype) if self.bias else None
        if self.layer_norm:
            ln_scale = self.param("ln_scale", nn.initializers.ones_init(), (n,), jnp.float32)
            ln_bias = self.param("ln_bias", nn.initializers.zeros_init(), (n,), jnp.float32)

        xh = jnp.concatenate([h.astype(self.dtype), x.astype(self.dtype)], axis=-1)
        dot_general = self.dot_general_cls() if self.dot_general_cls is not None else lax.dot_general
        fused = dot_general(xh, kernel.astype(self.dtype), (((xh.ndim - 1,), (0,)), ((), ())))
        if bias is not None:
            fused = fused + bias.astype(self.dtype)
        if self.layer_norm:
            # fp32 stats, dtype-preserving (same policy as the LayerNorm module)
            f32 = fused.astype(jnp.float32)
            mu = jnp.mean(f32, axis=-1, keepdims=True)
            var = jnp.var(f32, axis=-1, keepdims=True)
            f32 = (f32 - mu) * jax.lax.rsqrt(var + self.layer_norm_eps) * ln_scale + ln_bias
            fused = f32.astype(self.dtype)
        reset, cand, update = jnp.split(fused, 3, axis=-1)
        reset = jax.nn.sigmoid(reset)
        cand = jnp.tanh(reset * cand)
        update = jax.nn.sigmoid(update - 1)
        return update * cand + (1 - update) * h.astype(self.dtype)


class MultiEncoder(nn.Module):
    """Fuse cnn+mlp encoders by concatenating features (reference models.py:413-475)."""

    cnn_encoder: Optional[nn.Module]
    mlp_encoder: Optional[nn.Module]

    def __post_init__(self):
        super().__post_init__()
        if self.cnn_encoder is None and self.mlp_encoder is None:
            raise ValueError("There must be at least one encoder, both cnn and mlp encoders are None")

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array], *args, **kwargs) -> jax.Array:
        outs = []
        if self.cnn_encoder is not None:
            outs.append(self.cnn_encoder(obs, *args, **kwargs))
        if self.mlp_encoder is not None:
            outs.append(self.mlp_encoder(obs, *args, **kwargs))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


class MultiDecoder(nn.Module):
    """Merge cnn+mlp decoder outputs into one obs dict (reference models.py:478-504)."""

    cnn_decoder: Optional[nn.Module]
    mlp_decoder: Optional[nn.Module]

    def __post_init__(self):
        super().__post_init__()
        if self.cnn_decoder is None and self.mlp_decoder is None:
            raise ValueError("There must be an decoder, both cnn and mlp decoders are None")

    @nn.compact
    def __call__(self, x: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(x))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(x))
        return out
