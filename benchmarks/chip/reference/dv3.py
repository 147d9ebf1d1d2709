"""Plain reference of one DreamerV3 gradient step (Hafner et al. 2023, the
sheeprl recipe): straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, no flax, no optax, nothing imported from ``sheeprl_tpu``.

It owns the weights: :func:`param_spec` lays out every leaf from the sizes in the
configuration file and :func:`make_params` draws them from the seed. The benchmark
hands those arrays to the program (``build_agent(..., *_state)``), so the program
runs on weights it did not make and the reference takes nothing from it.

The posterior, the imagined prior and the actor sample with the Gumbel-max trick.
The noise is drawn from the same keys in ``sample_dtype`` (the dtype the
configuration's precision gives the logits, bfloat16 under ``bf16-mixed``): a
draw's bits depend on the dtype, so this is what "the same draws" takes.

``quant`` is the control's hook: every matmul and convolution passes both
operands through it. ``None`` is the reference; :func:`fake_fp8` is the nearest
precision below bfloat16.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Quant = Optional[Callable[[jax.Array], jax.Array]]


# ----------------------------------------------------------------------------- sizes
def sizes_from(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from the configuration file's ``sizes``."""
    s = dict(cfg)
    s["stoch_flat"] = s["stochastic_size"] * s["discrete_size"]
    s["latent"] = s["stoch_flat"] + s["recurrent_state_size"]
    s["stages"] = int(math.log2(s["image"]) - 2)
    s["embed_cnn"] = (2 ** (s["stages"] - 1)) * s["cnn_channels_multiplier"] * 16
    s["embed"] = s["embed_cnn"] + (s["dense_units"] if s["mlp_obs_dim"] else 0)
    return s


# --------------------------------------------------------------------------- weights
def _mlp_spec(prefix: Tuple[str, ...], in_dim: int, hidden: int, layers: int) -> Dict[Tuple[str, ...], Tuple]:
    spec = {}
    d = in_dim
    for i in range(layers):
        spec[prefix + (f"Dense_{i}", "kernel")] = ((d, hidden), "normal")
        spec[prefix + (f"LayerNorm_{i}", "LayerNorm_0", "scale")] = ((hidden,), "ones")
        spec[prefix + (f"LayerNorm_{i}", "LayerNorm_0", "bias")] = ((hidden,), "zeros")
        d = hidden
    return spec


def _head_spec(prefix: Tuple[str, ...], in_dim: int, hidden: int, layers: int, out: int, head: str = "head"):
    spec = _mlp_spec(prefix + ("params", "MLP_0"), in_dim, hidden, layers)
    spec[prefix + ("params", head, "kernel")] = ((hidden, out), "normal")
    spec[prefix + ("params", head, "bias")] = ((out,), "zeros")
    return spec


def actor_spec(s: Dict[str, Any]) -> Dict[Tuple[str, ...], Tuple]:
    return _head_spec(("actor",), s["latent"], s["dense_units"], s["mlp_layers"], s["actions"], head="head_0")


def param_spec(s: Dict[str, Any]) -> Dict[Tuple[str, ...], Tuple]:
    """Every leaf of the model as ``path -> (shape, init)``; the paths are the
    program's own parameter names, since it has to load them."""
    mult, stages, dense, layers = s["cnn_channels_multiplier"], s["stages"], s["dense_units"], s["mlp_layers"]
    deter, stoch, latent = s["recurrent_state_size"], s["stoch_flat"], s["latent"]
    wm = ("world_model",)
    spec: Dict[Tuple[str, ...], Tuple] = {}
    enc = wm + ("encoder", "params", "cnn_encoder", "CNN_0")
    c_in = s["image_channels"]
    for i in range(stages):
        c_out = (2**i) * mult
        spec[enc + (f"Conv_{i}", "kernel")] = ((4, 4, c_in, c_out), "normal")
        spec[enc + (f"LayerNorm_{i}", "LayerNorm_0", "scale")] = ((c_out,), "ones")
        spec[enc + (f"LayerNorm_{i}", "LayerNorm_0", "bias")] = ((c_out,), "zeros")
        c_in = c_out
    if s["mlp_obs_dim"]:
        spec.update(_mlp_spec(wm + ("encoder", "params", "mlp_encoder", "MLP_0"), s["mlp_obs_dim"], dense, layers))
    rec = wm + ("recurrent_model", "params")
    spec.update(_mlp_spec(rec + ("MLP_0",), stoch + s["actions"], dense, 1))
    spec[rec + ("LayerNormGRUCell_0", "kernel")] = ((deter + dense, 3 * deter), "normal")
    spec[rec + ("LayerNormGRUCell_0", "ln_scale")] = ((3 * deter,), "ones")
    spec[rec + ("LayerNormGRUCell_0", "ln_bias")] = ((3 * deter,), "zeros")
    spec.update(_head_spec(wm + ("representation_model",), deter + s["embed"], s["representation_hidden"], 1, stoch))
    spec.update(_head_spec(wm + ("transition_model",), deter, s["transition_hidden"], 1, stoch))
    dec = wm + ("observation_model", "params", "cnn_decoder")
    spec[dec + ("Dense_0", "kernel")] = ((latent, s["embed_cnn"]), "normal")
    spec[dec + ("Dense_0", "bias")] = ((s["embed_cnn"],), "zeros")
    c_in = (2 ** (stages - 1)) * mult
    outs = [(2**i) * mult for i in reversed(range(stages - 1))] + [s["image_channels"]]
    for i, c_out in enumerate(outs):
        # transposed-conv kernels are stored (kh, kw, out, in)
        spec[dec + ("DeCNN_0", f"ConvTranspose_{i}", "kernel")] = ((4, 4, c_out, c_in), "normal_t")
        if i < stages - 1:
            spec[dec + ("DeCNN_0", f"LayerNorm_{i}", "LayerNorm_0", "scale")] = ((c_out,), "ones")
            spec[dec + ("DeCNN_0", f"LayerNorm_{i}", "LayerNorm_0", "bias")] = ((c_out,), "zeros")
        else:
            spec[dec + ("DeCNN_0", f"ConvTranspose_{i}", "bias")] = ((c_out,), "zeros")
        c_in = c_out
    spec.update(_head_spec(wm + ("reward_model",), latent, dense, layers, s["bins"]))
    spec.update(_head_spec(wm + ("continue_model",), latent, dense, layers, 1))
    spec[wm + ("initial_recurrent_state",)] = ((deter,), "zeros")
    spec.update(actor_spec(s))
    spec.update(_head_spec(("critic",), latent, dense, layers, s["bins"]))
    return spec


def _draw(key, shape, init):
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    fan_in = math.prod(shape[:-1]) if init == "normal" else shape[0] * shape[1] * shape[3]
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def nest(flat: Dict[Tuple[str, ...], Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        d = out
        for p in path[:-1]:
            d = d.setdefault(p, {})
        d[path[-1]] = v
    return out


def make_params(spec: Dict[Tuple[str, ...], Tuple], seed) -> Dict[str, Any]:
    """All weights from the seed (trace it under one ``jax.jit``): kernels are
    normal with variance 1/fan_in, norm scales 1, biases 0. The target critic
    starts as a copy of the critic, as in the published algorithm."""
    root = jax.random.fold_in(jax.random.PRNGKey(20230110), seed)
    flat = {
        path: _draw(jax.random.fold_in(root, i), shape, init)
        for i, (path, (shape, init)) in enumerate(sorted(spec.items()))
    }
    params = nest(flat)
    params["target_critic"] = jax.tree_util.tree_map(lambda x: x + 0.0, params["critic"])
    return params


# ------------------------------------------------------------------------- numerics
def _rounded(x: jax.Array, dtype, top: float) -> jax.Array:
    """``x`` on the grid of the 8-bit float ``dtype`` with one scale per tensor (amax -> ``top``)."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def fake_fp8(x: jax.Array) -> jax.Array:
    """The control's precision, the usual fp8 training recipe: both operands of
    every matmul and convolution rounded to float8 e4m3 (amax -> 448) and, through
    ``fake_fp8.cotangent`` on the product, the cotangent that its two backward
    matmuls consume rounded to float8 e5m2 (amax -> 57344)."""
    return x + jax.lax.stop_gradient(_rounded(x, jnp.float8_e4m3fn, 448.0) - x)


@jax.custom_vjp
def _e5m2_cotangent(y):
    return y


_e5m2_cotangent.defvjp(lambda y: (y, None), lambda _, g: (_rounded(g, jnp.float8_e5m2, 57344.0),))
fake_fp8.cotangent = _e5m2_cotangent


def fake_bf16(x: jax.Array) -> jax.Array:
    """Operands rounded to bfloat16: the control where a configuration states float32."""
    return x + jax.lax.stop_gradient(x.astype(jnp.bfloat16).astype(jnp.float32) - x)


def _q(x, quant: Quant):
    return x if quant is None else quant(x)


def _q_out(y, quant: Quant):
    """The product of a quantised matmul: identity forward, the control's rounding of its cotangent."""
    cotangent = getattr(quant, "cotangent", None)
    return y if cotangent is None else cotangent(y)


def mm(x, w, quant: Quant):
    return _q_out(jnp.matmul(_q(x, quant), _q(w, quant), precision=HI), quant)


def dense(x, p, quant: Quant):
    y = mm(x, p["kernel"], quant)
    return y + p["bias"] if "bias" in p else y


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def mlp(x, p, layers: int, eps: float, quant: Quant, act=jax.nn.silu):
    for i in range(layers):
        x = dense(x, p[f"Dense_{i}"], quant)
        ln = p[f"LayerNorm_{i}"]["LayerNorm_0"]
        x = layer_norm(x, ln["scale"], ln["bias"], eps)
        x = act(x) if act is not None else x
    return x


def head(x, p, layers: int, eps: float, quant: Quant, name: str = "head"):
    p = p["params"]
    return dense(mlp(x, p["MLP_0"], layers, eps, quant), p[name], quant)


def symlog(x):
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x):
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1.0)


def unimix_logits(logits, discrete: int, unimix: float):
    """Logits of (1-u) softmax + u uniform, normalised, shape [..., S, discrete]."""
    logits = logits.reshape(*logits.shape[:-1], -1, discrete)
    probs = (1.0 - unimix) * jax.nn.softmax(logits, axis=-1) + unimix / discrete
    logits = jnp.log(jnp.clip(probs, 1e-12, None))
    return logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)


def gumbel_onehot(key, logits, sample_dtype):
    """One-hot Gumbel-max sample with straight-through gradient."""
    noisy = logits + jax.random.gumbel(key, logits.shape, sample_dtype).astype(jnp.float32)
    sample = jax.nn.one_hot(jnp.argmax(noisy, axis=-1), logits.shape[-1], dtype=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    return sample + probs - jax.lax.stop_gradient(probs)


def twohot_bins(n: int):
    return jnp.linspace(-20.0, 20.0, n)


def twohot_mean(logits):
    return symexp(jnp.sum(jax.nn.softmax(logits, axis=-1) * twohot_bins(logits.shape[-1]), axis=-1, keepdims=True))


def twohot_log_prob(logits, x):
    """x: [..., 1] -> [...]."""
    bins = twohot_bins(logits.shape[-1])
    n = bins.shape[0]
    x = symlog(x)
    below = jnp.sum((bins <= x).astype(jnp.int32), axis=-1, keepdims=True) - 1
    above = jnp.clip(below + 1, 0, n - 1)
    below = jnp.clip(below, 0, n - 1)
    equal = below == above
    d_below = jnp.where(equal, 1.0, jnp.abs(bins[below] - x))
    d_above = jnp.where(equal, 1.0, jnp.abs(bins[above] - x))
    total = d_below + d_above
    target = (
        jax.nn.one_hot(below[..., 0], n) * (d_above / total) + jax.nn.one_hot(above[..., 0], n) * (d_below / total)
    )
    return jnp.sum(target * jax.nn.log_softmax(logits, axis=-1), axis=-1)


def categorical_kl(p_logits, q_logits):
    p_log = jax.nn.log_softmax(p_logits, axis=-1)
    q_log = jax.nn.log_softmax(q_logits, axis=-1)
    return jnp.sum(jnp.exp(p_log) * (p_log - q_log), axis=(-2, -1))


# ---------------------------------------------------------------------------- layers
_DN = ("NHWC", "HWIO", "NHWC")


def encoder(p, rgb, vec, s, quant: Quant):
    """rgb [N, C, H, W] float in [-0.5, 0.5], vec [N, D] -> [N, embed]."""
    eps = s["layer_norm_eps"]
    cnn = p["params"]["cnn_encoder"]["CNN_0"]
    x = jnp.transpose(rgb, (0, 2, 3, 1))
    for i in range(s["stages"]):
        x = _q_out(jax.lax.conv_general_dilated(
            _q(x, quant), _q(cnn[f"Conv_{i}"]["kernel"], quant), (2, 2), [(1, 1), (1, 1)],
            dimension_numbers=_DN, precision=HI,
        ), quant)
        ln = cnn[f"LayerNorm_{i}"]["LayerNorm_0"]
        x = jax.nn.silu(layer_norm(x, ln["scale"], ln["bias"], eps))
    x = jnp.transpose(x, (0, 3, 1, 2)).reshape(x.shape[0], -1)
    if s["mlp_obs_dim"]:
        v = mlp(symlog(vec), p["params"]["mlp_encoder"]["MLP_0"], s["mlp_layers"], eps, quant)
        x = jnp.concatenate([x, v], axis=-1)
    return x


def decoder(p, latent, s, quant: Quant):
    """latent [N, L] -> reconstructed rgb [N, C, H, W]."""
    eps = s["layer_norm_eps"]
    p = p["params"]["cnn_decoder"]
    x = dense(latent, p["Dense_0"], quant)
    x = jnp.transpose(x.reshape(-1, (2 ** (s["stages"] - 1)) * s["cnn_channels_multiplier"], 4, 4), (0, 2, 3, 1))
    de = p["DeCNN_0"]
    for i in range(s["stages"]):
        ct = de[f"ConvTranspose_{i}"]
        x = _q_out(jax.lax.conv_transpose(
            _q(x, quant), _q(ct["kernel"], quant), (2, 2), [(2, 2), (2, 2)],
            dimension_numbers=_DN, transpose_kernel=True, precision=HI,
        ), quant)
        if i < s["stages"] - 1:
            ln = de[f"LayerNorm_{i}"]["LayerNorm_0"]
            x = jax.nn.silu(layer_norm(x, ln["scale"], ln["bias"], eps))
        else:
            x = x + ct["bias"]
    return jnp.transpose(x, (0, 3, 1, 2))


def recurrent(wm, stoch, action, h, s, quant: Quant):
    p = wm["recurrent_model"]["params"]
    eps = s["layer_norm_eps"]
    feat = mlp(jnp.concatenate([stoch, action], axis=-1), p["MLP_0"], 1, eps, quant, act=None)
    g = p["LayerNormGRUCell_0"]
    fused = mm(jnp.concatenate([h, feat], axis=-1), g["kernel"], quant)
    fused = layer_norm(fused, g["ln_scale"], g["ln_bias"], eps)
    reset, cand, update = jnp.split(fused, 3, axis=-1)
    cand = jnp.tanh(jax.nn.sigmoid(reset) * cand)
    update = jax.nn.sigmoid(update - 1.0)
    return update * cand + (1.0 - update) * h


def prior_logits(wm, h, s, quant: Quant):
    return unimix_logits(
        head(h, wm["transition_model"], 1, s["layer_norm_eps"], quant), s["discrete_size"], s["unimix"]
    )


def posterior_logits(wm, h, embed, s, quant: Quant):
    x = jnp.concatenate([h, embed], axis=-1)
    return unimix_logits(
        head(x, wm["representation_model"], 1, s["layer_norm_eps"], quant), s["discrete_size"], s["unimix"]
    )


def actor_logits(p, latent, s, quant: Quant):
    """Normalised action logits with the uniform mixture, [..., A]."""
    logits = head(latent, p, s["mlp_layers"], s["layer_norm_eps"], quant, name="head_0")
    return unimix_logits(logits, s["actions"], s["unimix"])[..., 0, :]


# ------------------------------------------------------------------------ optimizer
def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"count": jnp.zeros((), jnp.int32), "mu": zeros, "nu": zeros}


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree)))


def clipped_adam(grads, state, params, lr, eps, clip, b1=0.9, b2=0.999):
    """Global-norm clipping, then Adam. Returns (new params, new state, clipped grads, norm before clipping)."""
    norm = global_norm(grads)
    grads = jax.tree_util.tree_map(lambda g: jnp.where(norm < clip, g, g / norm * clip), grads)
    count = state["count"] + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"], grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), params, mu, nu
    )
    return new, {"count": count, "mu": mu, "nu": nu}, grads, norm


# ------------------------------------------------------------------------- the step
def init_state(params):
    return {
        "params": params,
        "opt": {k: adam_init(params[k]) for k in ("world_model", "actor", "critic")},
        "moments": (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        "counter": jnp.zeros((), jnp.int32),
    }


def prepare_batch(batch, s):
    """[T, B, ...] arrays of the replay rows -> what the losses consume."""
    rgb = batch["rgb"].astype(jnp.float32) / 255.0 - 0.5
    vec = batch[s["mlp_obs_key"]].astype(jnp.float32) if s["mlp_obs_dim"] else None
    is_first = batch["is_first"].astype(jnp.float32).at[0].set(1.0)
    actions = batch["actions"].astype(jnp.float32)
    prev_actions = jnp.concatenate([jnp.zeros_like(actions[:1]), actions[:-1]], axis=0)
    rewards = batch["rewards"].astype(jnp.float32)
    continues = 1.0 - batch["terminated"].astype(jnp.float32)
    return rgb, vec, is_first, prev_actions, rewards, continues


def world_loss(wm, batch, key, s, quant: Quant, sample_dtype):
    rgb, vec, is_first, prev_actions, rewards, continues = batch
    T, B = rgb.shape[:2]
    flat = lambda x: x.reshape(T * B, *x.shape[2:])
    embed = jax.checkpoint(lambda p, a, b: encoder(p, a, b, s, quant))(
        wm["encoder"], flat(rgb), None if vec is None else flat(vec)
    ).reshape(T, B, -1)
    init_h = jnp.tanh(wm["initial_recurrent_state"])

    def step(carry, xs):
        h, stoch = carry
        action, emb, first, k = xs
        _k_prior, k_post = jax.random.split(k)
        action = (1.0 - first) * action
        h0 = jnp.broadcast_to(init_h, h.shape)
        l0 = prior_logits(wm, h0, s, quant)
        stoch0 = jax.nn.one_hot(jnp.argmax(l0, axis=-1), s["discrete_size"]).reshape(stoch.shape)
        h = (1.0 - first) * h + first * h0
        stoch = (1.0 - first) * stoch + first * stoch0
        h = recurrent(wm, stoch, action, h, s, quant)
        pri = prior_logits(wm, h, s, quant)
        post = posterior_logits(wm, h, emb, s, quant)
        z = gumbel_onehot(k_post, post, sample_dtype)
        return (h, z.reshape(stoch.shape)), (h, z, post, pri)

    carry0 = (jnp.zeros((B, s["recurrent_state_size"])), jnp.zeros((B, s["stoch_flat"])))
    keys = jax.random.split(key, T)
    _, (hs, zs, post, pri) = jax.lax.scan(step, carry0, (prev_actions, embed, is_first, keys))
    latent = jnp.concatenate([zs.reshape(T, B, -1), hs], axis=-1)
    recon = jax.checkpoint(lambda p, x: decoder(p, x, s, quant))(wm["observation_model"], flat(latent))
    obs_lp = -jnp.sum(jnp.square(recon.reshape(rgb.shape) - rgb), axis=(-3, -2, -1))
    eps, layers = s["layer_norm_eps"], s["mlp_layers"]
    rew_lp = twohot_log_prob(head(latent, wm["reward_model"], layers, eps, quant), rewards)
    cont_logit = head(latent, wm["continue_model"], layers, eps, quant)
    cont_lp = -jnp.sum(
        jnp.clip(cont_logit, 0, None) - cont_logit * continues + jnp.log1p(jnp.exp(-jnp.abs(cont_logit))), axis=-1
    )
    sg = jax.lax.stop_gradient
    dyn = s["kl_dynamic"] * jnp.maximum(categorical_kl(sg(post), pri), s["kl_free_nats"])
    rep = s["kl_representation"] * jnp.maximum(categorical_kl(post, sg(pri)), s["kl_free_nats"])
    loss = jnp.mean(s["kl_regularizer"] * (dyn + rep) - obs_lp - rew_lp - s["continue_scale_factor"] * cont_lp)
    entropy = lambda logits: -jnp.sum(jnp.exp(logits) * logits, axis=(-2, -1)).mean()
    stats = {
        "observation": jnp.mean(-obs_lp), "reward": jnp.mean(-rew_lp), "continue": jnp.mean(-cont_lp),
        "kl": jnp.mean(categorical_kl(post, pri)), "post_entropy": entropy(post), "prior_entropy": entropy(pri),
    }
    return loss, (zs, hs, stats)


def lambda_values(rewards, values, continues, lmbda):
    interm = rewards + continues * values * (1.0 - lmbda)
    out = []
    nxt = values[-1]
    for t in reversed(range(rewards.shape[0])):
        nxt = interm[t] + continues[t] * lmbda * nxt
        out.append(nxt)
    return jnp.stack(out[::-1], axis=0)


def imagine(wm, actor_p, start_z, start_h, k_img0, k_img, s, quant: Quant, sample_dtype):
    """Forward-only rollout of ``horizon`` steps from every posterior:
    trajectories [H+1, N, L] and the sampled one-hot actions [H+1, N, A]."""
    policy = lambda p, latent, key: gumbel_onehot(
        jax.random.split(key, 1)[0], actor_logits(p, latent, s, quant), sample_dtype)
    latent0 = jnp.concatenate([start_z, start_h], axis=-1)
    action0 = policy(actor_p, latent0, k_img0)

    def step(carry, k):
        z, h, a = carry
        k_step, k_act = jax.random.split(k)
        h = recurrent(wm, z, a, h, s, quant)
        z = gumbel_onehot(k_step, prior_logits(wm, h, s, quant), sample_dtype).reshape(z.shape)
        latent = jnp.concatenate([z, h], axis=-1)
        a = policy(actor_p, latent, k_act)
        return (z, h, a), (latent, a)

    _, (latents, acts) = jax.lax.scan(step, (start_z, start_h, action0), jax.random.split(k_img, s["horizon"]))
    return jnp.concatenate([latent0[None], latents], 0), jnp.concatenate([action0[None], acts], 0)


def returns_and_moments(params, new_wm, traj, true_continue, moments, s, quant: Quant):
    """Lambda returns, discount, baseline and the percentile normaliser."""
    eps, layers = s["layer_norm_eps"], s["mlp_layers"]
    values = twohot_mean(head(traj, params["critic"], layers, eps, quant))
    rewards = twohot_mean(head(traj, new_wm["reward_model"], layers, eps, quant))
    cont = (jax.nn.sigmoid(head(traj, new_wm["continue_model"], layers, eps, quant)) > 0.5).astype(jnp.float32)
    cont = jnp.concatenate([true_continue[None], cont[1:]], axis=0)
    lam = lambda_values(rewards[1:], values[1:], cont[1:] * s["gamma"], s["lmbda"])
    discount = jnp.cumprod(cont * s["gamma"], axis=0) / s["gamma"]
    low = s["moments_decay"] * moments[0] + (1 - s["moments_decay"]) * jnp.quantile(lam, s["moments_low"])
    high = s["moments_decay"] * moments[1] + (1 - s["moments_decay"]) * jnp.quantile(lam, s["moments_high"])
    invscale = jnp.maximum(1.0 / s["moments_max"], high - low)
    advantage = (lam - low) / invscale - (values[:-1] - low) / invscale
    return lam, discount, advantage, (low, high)


def critic_update(params, opt, target, traj, lam, discount, s, quant: Quant):
    eps, layers = s["layer_norm_eps"], s["mlp_layers"]
    target_values = twohot_mean(head(traj[:-1], target, layers, eps, quant))

    def loss_fn(p):
        @jax.checkpoint  # one imagined step at a time, so the float32 activations of all of them never coexist
        def per_step(xs):
            x, lam_t, target_t = xs
            logits = head(x, p, layers, eps, quant)
            return -twohot_log_prob(logits, lam_t) - twohot_log_prob(logits, target_t)

        per = jax.lax.map(per_step, (traj[:-1], lam, target_values)) * discount[:-1][..., 0]
        return jnp.mean(per)

    loss, grads = jax.value_and_grad(loss_fn)(params["critic"])
    new, opt, grads, norm = clipped_adam(grads, opt, params["critic"], s["critic_lr"], s["critic_eps"], s["critic_clip"])
    return loss, new, opt, grads, norm


def actor_loss_dv3(actor_p, traj, actions, advantage, discount, s, quant: Quant):
    logits = actor_logits(actor_p, traj, s, quant)
    log_prob = jnp.sum(actions * logits, axis=-1)
    entropy = -jnp.sum(jnp.exp(logits) * logits, axis=-1)
    objective = log_prob[..., None][:-1] * advantage + s["ent_coef"] * entropy[..., None][:-1]
    return -jnp.mean(discount[:-1] * objective)


def train_step(state, raw_batch, key, s, quant: Quant = None, sample_dtype=jnp.bfloat16):
    """One gradient step. Returns (new state, {losses, clipped grads per group})."""
    params, opt = state["params"], state["opt"]
    batch = prepare_batch(raw_batch, s)
    key = jax.random.split(key, 1)[0]  # the program scans over one gradient step per call
    k_wm, k_img0, k_img, _ = jax.random.split(key, 4)
    tau = jnp.where(state["counter"] == 0, 1.0, s["critic_tau"])
    target = jax.tree_util.tree_map(lambda p, t: tau * p + (1.0 - tau) * t, params["critic"], params["target_critic"])

    (w_loss, (zs, hs, stats)), w_grads = jax.value_and_grad(world_loss, has_aux=True)(
        params["world_model"], batch, k_wm, s, quant, sample_dtype
    )
    new_wm, w_opt, w_grads, w_norm = clipped_adam(
        w_grads, opt["world_model"], params["world_model"], s["world_lr"], s["world_eps"], s["world_clip"]
    )

    sg = jax.lax.stop_gradient
    start_z = sg(zs).reshape(-1, s["stoch_flat"])
    start_h = sg(hs).reshape(-1, s["recurrent_state_size"])
    true_continue = batch[5].reshape(-1, 1)
    traj, actions = imagine(new_wm, params["actor"], start_z, start_h, k_img0, k_img, s, quant, sample_dtype)
    traj, actions = sg(traj), sg(actions)
    lam, discount, advantage, moments = returns_and_moments(
        params, new_wm, traj, true_continue, state["moments"], s, quant
    )
    a_loss, a_grads = jax.value_and_grad(actor_loss_dv3)(params["actor"], traj, actions, advantage, discount, s, quant)
    new_actor, a_opt, a_grads, a_norm = clipped_adam(
        a_grads, opt["actor"], params["actor"], s["actor_lr"], s["actor_eps"], s["actor_clip"]
    )
    c_loss, new_critic, c_opt, c_grads, c_norm = critic_update(
        params, opt["critic"], target, traj, lam, discount, s, quant
    )

    new_state = {
        "params": {"world_model": new_wm, "actor": new_actor, "critic": new_critic, "target_critic": target},
        "opt": {"world_model": w_opt, "actor": a_opt, "critic": c_opt},
        "moments": moments,
        "counter": state["counter"] + 1,
    }
    out = {
        "losses": {"world_model": w_loss, "policy": a_loss, "value": c_loss, **stats,
                   "grad_norm.world_model": w_norm, "grad_norm.actor": a_norm, "grad_norm.critic": c_norm},
        "grads": {"world_model": w_grads, "actor": a_grads, "critic": c_grads},
    }
    return new_state, out


def leaf_norms(tree) -> Dict[str, np.ndarray]:
    """``path -> l2 norm`` for every leaf, the readings `check.py` compares."""
    return {
        jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]
    }
