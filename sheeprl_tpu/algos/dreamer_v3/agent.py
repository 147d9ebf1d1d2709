"""DreamerV3 agent: encoders/decoders, RSSM, actor, critic, player (flax + lax.scan).

Parity targets (reference sheeprl/algos/dreamer_v3/agent.py): CNNEncoder (:42),
MLPEncoder (:100), CNNDecoder (:154), MLPDecoder (:229), RecurrentModel (:281),
RSSM (:344), DecoupledRSSM (:501), PlayerDV3 (:596), Actor (:694), build_agent (:935),
Hafner init (dreamer_v3/utils.py:init_weights/uniform_init_weights).

TPU-first design decisions:
- The RSSM is a set of small flax modules (recurrent cell, representation, transition)
  composed by *pure scan functions* (`rssm_dynamic_scan`, `rssm_imagination_scan`)
  instead of a stateful module with Python loops: the T=64 dynamic unroll and the H=15
  imagination unroll each compile to ONE fused `lax.scan` whose per-step compute is a
  few MXU matmuls (the reference loops in Python, dreamer_v3.py:138-151, 243-252).
- Params live in a plain dict pytree (`wm_params`), so the world model / actor /
  critic are optax-updatable leaves with no module-wrapper state.
- Hafner initialization maps exactly onto `variance_scaling`: trunc-normal
  fan-avg scale 1.0 for trunks; fan-avg uniform (scale 1.0 or 0.0) for output heads.
- The player's policy step is a single jitted pure function over explicit state
  (recurrent/stochastic/actions), so rollout latency is one host->device dispatch.
"""

from __future__ import annotations

import copy
from math import prod
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.models.models import MLP, TAPS, CNN, DeCNN, LayerNorm, LayerNormGRUCell, TapDot, kernel_taps
from sheeprl_tpu.ops.distributions import (
    Independent,
    Normal,
    OneHotCategoricalStraightThrough,
    TanhNormal,
)
from sheeprl_tpu.utils.utils import host_float32, resolve_actor_cls, symlog

# Hafner initializers (reference dreamer_v3/utils.py:init_weights / uniform_init_weights):
# trunc-normal with std = sqrt(1/fan_avg)/0.8796...  == variance_scaling truncated_normal;
# heads use uniform with limit sqrt(3*scale/fan_avg) == variance_scaling uniform.
hafner_trunc_init = nn.initializers.variance_scaling(1.0, "fan_avg", "truncated_normal")


def hafner_uniform_init(scale: float):
    if scale == 0.0:
        return nn.initializers.zeros_init()
    return nn.initializers.variance_scaling(scale, "fan_avg", "uniform")


def uniform_mix(logits: jax.Array, discrete: int, unimix: float) -> jax.Array:
    """1% uniform mixture over each categorical (reference agent.py:437-449).

    Input/output logits shape ``[..., stoch*discrete]``.
    """
    shape = logits.shape
    logits = logits.reshape(*shape[:-1], -1, discrete)
    if unimix > 0.0:
        probs = jax.nn.softmax(logits, axis=-1)
        uniform = jnp.ones_like(probs) / discrete
        probs = (1 - unimix) * probs + unimix * uniform
        logits = jnp.log(jnp.clip(probs, 1e-12, None))
    return logits.reshape(shape)


def compute_stochastic_state(
    logits: jax.Array, discrete: int, key: Optional[jax.Array] = None, sample: bool = True
) -> jax.Array:
    """Sample (straight-through) or take the mode of the categorical stochastic state.

    Reference: sheeprl/algos/dreamer_v2/utils.py:44-63. Input ``[..., stoch*discrete]``,
    output ``[..., stoch, discrete]``.
    """
    logits = logits.reshape(*logits.shape[:-1], -1, discrete)
    dist = OneHotCategoricalStraightThrough(logits=logits)
    if sample:
        return dist.rsample(key)
    return dist.mode


class CNNEncoder(nn.Module):
    """4-stage stride-2 image encoder, 64x64 -> 4x4 (reference agent.py:42-99).

    Multiple image keys are concatenated on the channel dim. Output is flattened.
    """

    keys: Sequence[str]
    input_channels: Sequence[int]
    image_size: Tuple[int, int]
    channels_multiplier: int
    layer_norm: bool = True
    layer_norm_eps: float = 1e-3
    activation: str = "silu"
    stages: int = 4
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def output_dim(self) -> int:
        h = self.image_size[0] // (2**self.stages)
        w = self.image_size[1] // (2**self.stages)
        return (2 ** (self.stages - 1)) * self.channels_multiplier * h * w

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-3)
        batch_shape = x.shape[:-3]
        x = x.reshape(-1, *x.shape[-3:])
        x = CNN(
            input_channels=sum(self.input_channels),
            hidden_channels=[(2**i) * self.channels_multiplier for i in range(self.stages)],
            layer_args={"kernel_size": 4, "stride": 2, "padding": 1, "bias": not self.layer_norm},
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_args={"eps": self.layer_norm_eps},
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=hafner_trunc_init,
        )(x)
        x = x.reshape(x.shape[0], -1)
        return x.reshape(*batch_shape, x.shape[-1])


class MLPEncoder(nn.Module):
    """Vector encoder with symlog inputs (reference agent.py:100-151)."""

    keys: Sequence[str]
    input_dims: Sequence[int]
    mlp_layers: int = 4
    dense_units: int = 512
    layer_norm: bool = True
    layer_norm_eps: float = 1e-3
    activation: str = "silu"
    symlog_inputs: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def output_dim(self) -> int:
        return self.dense_units

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([symlog(obs[k]) if self.symlog_inputs else obs[k] for k in self.keys], axis=-1)
        return MLP(
            input_dims=sum(self.input_dims),
            output_dim=None,
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_args={"eps": self.layer_norm_eps},
            use_bias=not self.layer_norm,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=hafner_trunc_init,
        )(x)


class MultiEncoderDV3(nn.Module):
    """Concatenate CNN and MLP features (reference MultiEncoder, models.py:413-475)."""

    cnn_encoder: Optional[CNNEncoder]
    mlp_encoder: Optional[MLPEncoder]

    @property
    def output_dim(self) -> int:
        out = 0
        if self.cnn_encoder is not None:
            out += self.cnn_encoder.output_dim
        if self.mlp_encoder is not None:
            out += self.mlp_encoder.output_dim
        return out

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        outs = []
        if self.cnn_encoder is not None:
            outs.append(self.cnn_encoder(obs))
        if self.mlp_encoder is not None:
            outs.append(self.mlp_encoder(obs))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


class CNNDecoder(nn.Module):
    """Inverse of CNNEncoder: latent -> 4x4 features -> image dict (reference agent.py:154-228)."""

    keys: Sequence[str]
    output_channels: Sequence[int]
    channels_multiplier: int
    cnn_encoder_output_dim: int
    image_size: Tuple[int, int]
    layer_norm: bool = True
    layer_norm_eps: float = 1e-3
    activation: str = "silu"
    stages: int = 4
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent_states: jax.Array) -> Dict[str, jax.Array]:
        batch_shape = latent_states.shape[:-1]
        x = latent_states.reshape(-1, latent_states.shape[-1])
        x = nn.Dense(
            self.cnn_encoder_output_dim,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=hafner_trunc_init,
        )(x)
        h0 = self.image_size[0] // (2**self.stages)
        w0 = self.image_size[1] // (2**self.stages)
        x = x.reshape(-1, (2 ** (self.stages - 1)) * self.channels_multiplier, h0, w0)
        out_ch = sum(self.output_channels)
        x = DeCNN(
            input_channels=(2 ** (self.stages - 1)) * self.channels_multiplier,
            hidden_channels=[(2**i) * self.channels_multiplier for i in reversed(range(self.stages - 1))]
            + [out_ch],
            layer_args=[
                {"kernel_size": 4, "stride": 2, "padding": 1, "bias": not self.layer_norm}
                for _ in range(self.stages - 1)
            ]
            + [{"kernel_size": 4, "stride": 2, "padding": 1}],
            activation=[self.activation] * (self.stages - 1) + [None],
            layer_norm=[self.layer_norm] * (self.stages - 1) + [False],
            norm_args={"eps": self.layer_norm_eps},
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=[hafner_trunc_init] * (self.stages - 1) + [hafner_uniform_init(1.0)],
        )(x)
        x = x.reshape(*batch_shape, out_ch, *self.image_size)
        out: Dict[str, jax.Array] = {}
        start = 0
        for k, ch in zip(self.keys, self.output_channels):
            out[k] = x[..., start : start + ch, :, :]
            start += ch
        return out


class MLPDecoder(nn.Module):
    """Inverse of MLPEncoder: latent -> vector dict (reference agent.py:229-280)."""

    keys: Sequence[str]
    output_dims: Sequence[int]
    mlp_layers: int = 4
    dense_units: int = 512
    layer_norm: bool = True
    layer_norm_eps: float = 1e-3
    activation: str = "silu"
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent_states: jax.Array) -> Dict[str, jax.Array]:
        x = MLP(
            input_dims=latent_states.shape[-1],
            output_dim=None,
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_args={"eps": self.layer_norm_eps},
            use_bias=not self.layer_norm,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=hafner_trunc_init,
        )(latent_states)
        return {
            k: nn.Dense(
                dim,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=hafner_uniform_init(1.0),
                name=f"head_{k}",
            )(x)
            for k, dim in zip(self.keys, self.output_dims)
        }


class MultiDecoderDV3(nn.Module):
    cnn_decoder: Optional[CNNDecoder]
    mlp_decoder: Optional[MLPDecoder]

    @nn.compact
    def __call__(self, latent_states: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(latent_states))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(latent_states))
        return out


class RecurrentModel(nn.Module):
    """MLP projection + LayerNorm GRU (reference agent.py:281-343).

    One fused input matmul + one fused GRU matmul per step — both MXU-friendly.
    """

    input_size: int
    recurrent_state_size: int
    dense_units: int
    layer_norm: bool = True
    layer_norm_eps: float = 1e-3
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        feat = MLP(
            input_dims=self.input_size,
            output_dim=None,
            hidden_sizes=[self.dense_units],
            activation=None,
            layer_norm=self.layer_norm,
            norm_args={"eps": self.layer_norm_eps},
            use_bias=not self.layer_norm,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=hafner_trunc_init,
            dot_general_cls=TapDot,
        )(x)
        return LayerNormGRUCell(
            hidden_size=self.recurrent_state_size,
            bias=False,
            layer_norm=True,
            layer_norm_eps=self.layer_norm_eps,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=hafner_trunc_init,
            dot_general_cls=TapDot,
        )(feat, recurrent_state)


class MLPWithHead(nn.Module):
    """MLP trunk + linear head with Hafner head init (representation/transition/
    reward/continue/critic share this shape; reference builds them as plain MLPs with
    per-layer init overrides, agent.py:1021-1180)."""

    input_dim: int
    hidden_sizes: Sequence[int]
    output_dim: int
    activation: str = "silu"
    layer_norm: bool = True
    layer_norm_eps: float = 1e-3
    head_init_scale: float = 1.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        if len(self.hidden_sizes) > 0:
            x = MLP(
                input_dims=self.input_dim,
                output_dim=None,
                hidden_sizes=self.hidden_sizes,
                activation=self.activation,
                layer_norm=self.layer_norm,
                norm_args={"eps": self.layer_norm_eps},
                use_bias=not self.layer_norm,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=hafner_trunc_init,
                dot_general_cls=TapDot,
            )(x)
        head_init = (
            hafner_uniform_init(self.head_init_scale)
            if self.head_init_scale >= 0
            else nn.initializers.lecun_normal()
        )
        return nn.Dense(
            self.output_dim,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=head_init,
            dot_general_cls=TapDot,
            name="head",
        )(x)


class Actor(nn.Module):
    """DV3 actor (reference agent.py:694-847).

    Returns the raw pre-distribution outputs (one per discrete action head, or a
    single mean/std tensor for continuous); distribution math lives in `ActorOutput`.
    """

    latent_state_size: int
    actions_dim: Sequence[int]
    is_continuous: bool
    distribution: str = "auto"
    init_std: float = 2.0
    min_std: float = 0.1
    max_std: float = 1.0
    dense_units: int = 1024
    mlp_layers: int = 5
    layer_norm: bool = True
    layer_norm_eps: float = 1e-3
    activation: str = "silu"
    unimix: float = 0.01
    action_clip: float = 1.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    # rollout-time masked sampling is an actor property, not a player branch
    uses_action_mask: bool = False

    def resolved_distribution(self) -> str:
        dist = self.distribution.lower()
        if dist not in ("auto", "normal", "tanh_normal", "discrete", "scaled_normal"):
            raise ValueError(
                "The distribution must be on of: `auto`, `discrete`, `normal`, `tanh_normal` and `scaled_normal`. "
                f"Found: {dist}"
            )
        if dist == "discrete" and self.is_continuous:
            raise ValueError("You have choose a discrete distribution but `is_continuous` is true")
        if dist == "auto":
            dist = "scaled_normal" if self.is_continuous else "discrete"
        return dist

    def sample(self, pre_dist: List[jax.Array], key: jax.Array, greedy: bool = False, mask=None) -> List[jax.Array]:
        """Turn raw head outputs into env actions; subclasses may consume ``mask``."""
        return ActorOutput(self, pre_dist).sample_actions(key, greedy=greedy)

    @nn.compact
    def __call__(self, state: jax.Array) -> List[jax.Array]:
        x = MLP(
            input_dims=self.latent_state_size,
            output_dim=None,
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_norm=self.layer_norm,
            norm_args={"eps": self.layer_norm_eps},
            use_bias=not self.layer_norm,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_init=hafner_trunc_init,
        )(state)
        if self.is_continuous:
            return [
                nn.Dense(
                    int(np.sum(self.actions_dim)) * 2,
                    dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    kernel_init=hafner_uniform_init(1.0),
                    name="head_0",
                )(x)
            ]
        return [
            nn.Dense(
                dim,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                kernel_init=hafner_uniform_init(1.0),
                name=f"head_{i}",
            )(x)
            for i, dim in enumerate(self.actions_dim)
        ]


class MinedojoActor(Actor):
    """DV3 actor for MineDojo (reference agent.py:848-934): same parameters as
    `Actor`, but rollout-time sampling applies the env-provided action masks —
    see `sample_minedojo_actions`. Selected via ``cfg.algo.actor.cls``."""

    uses_action_mask: bool = True

    def sample(self, pre_dist: List[jax.Array], key: jax.Array, greedy: bool = False, mask=None) -> List[jax.Array]:
        return sample_minedojo_actions(self, pre_dist, mask, key, greedy=greedy)


def sample_minedojo_actions(
    actor,
    pre_dist: List[jax.Array],
    mask: Optional[Dict[str, jax.Array]],
    key: jax.Array,
    greedy: bool = False,
) -> List[jax.Array]:
    """Sequential masked sampling over MineDojo's three action heads
    (reference MinedojoActor.forward, agent.py:883-934).

    Head 0 (action type) is masked by ``mask_action_type``; head 1 (craft
    target) is masked by ``mask_craft_smelt`` only when the sampled macro is 15
    (craft); head 2 (equip/place/destroy target) is masked by
    ``mask_equip_place`` for macros 16/17 and ``mask_destroy`` for macro 18.
    The reference loops over every [t, b] element in Python; here the
    conditional masking is a batched `jnp.where` on the logits.
    """
    if mask is None:
        return ActorOutput(actor, pre_dist).sample_actions(key, greedy=greedy)

    keys = jax.random.split(key, len(pre_dist))
    actions: List[jax.Array] = []
    functional_action = None
    for i, logits in enumerate(pre_dist):
        logits = uniform_mix(logits, logits.shape[-1], getattr(actor, "unimix", 0.0))
        logits = minedojo_mask_logits(logits, i, mask, functional_action)
        dist = OneHotCategoricalStraightThrough(logits=logits)
        actions.append(dist.mode if greedy else dist.rsample(keys[i]))
        if functional_action is None:
            functional_action = actions[0].argmax(axis=-1)
    return actions


def minedojo_mask_logits(
    logits: jax.Array, head: int, mask: Dict[str, jax.Array], functional_action: Optional[jax.Array]
) -> jax.Array:
    """-inf-mask one MineDojo head's logits per the env constraints.

    Head 0: ``mask_action_type``. Head 1: ``mask_craft_smelt`` when the sampled
    macro is 15 (craft). Head 2: ``mask_equip_place`` for macros 16/17,
    ``mask_destroy`` for macro 18. Single source for the macro->mask mapping
    (used by DV3/DV2 sampling AND the DV2 masked exploration noise); batched
    `jnp.where` instead of the reference's per-[t,b] Python loops.
    """

    def masked(m):
        m = jnp.broadcast_to(jnp.asarray(m, dtype=bool), logits.shape)
        return jnp.where(m, logits, -jnp.inf)

    if head == 0:
        return masked(mask["mask_action_type"])
    if head == 1:
        return jnp.where((functional_action == 15)[..., None], masked(mask["mask_craft_smelt"]), logits)
    is_equip_place = ((functional_action == 16) | (functional_action == 17))[..., None]
    out = jnp.where(is_equip_place, masked(mask["mask_equip_place"]), logits)
    return jnp.where((functional_action == 18)[..., None], masked(mask["mask_destroy"]), out)


class ActorOutput:
    """Distribution wrapper over the actor's raw head outputs.

    Mirrors the (actions, dists) tuple the reference actor returns (agent.py:783-847)
    with explicit PRNG keys.
    """

    def __init__(self, actor: Actor, pre_dist: List[jax.Array]):
        self.actor = actor
        self.dist_type = actor.resolved_distribution()
        self.pre_dist = pre_dist
        if actor.is_continuous:
            mean, std = jnp.split(pre_dist[0], 2, axis=-1)
            if self.dist_type == "tanh_normal":
                mean = 5 * jnp.tanh(mean / 5)
                std = jax.nn.softplus(std + actor.init_std) + actor.min_std
                self.dists = [Independent(TanhNormal(mean, std), 1)]
            elif self.dist_type == "normal":
                self.dists = [Independent(Normal(mean, std), 1)]
            else:  # scaled_normal
                std = (actor.max_std - actor.min_std) * jax.nn.sigmoid(std + actor.init_std) + actor.min_std
                self.dists = [Independent(Normal(jnp.tanh(mean), std), 1)]
        else:
            self.dists = [
                OneHotCategoricalStraightThrough(logits=uniform_mix(logits, logits.shape[-1], getattr(actor, "unimix", 0.0)))
                for logits in pre_dist
            ]

    def sample_actions(self, key: jax.Array, greedy: bool = False) -> List[jax.Array]:
        return self.sample_actions_with_raw(key, greedy=greedy)[0]

    def sample_actions_with_raw(self, key: jax.Array, greedy: bool = False):
        """(clipped actions, raw pre-clip samples).

        The raw sample is the point at which a score-function (REINFORCE)
        estimator must evaluate log-prob: for a saturated continuous policy the
        clip rescaling moves ~half the samples onto the boundary, and log-prob
        at the CLIPPED point no longer estimates the sampled policy's score
        (walker_walk measures 40-46% saturation, benchmarks/WALKER_WALK_NOTES.md).
        The env/dynamics always consume the clipped actions.
        """
        if self.actor.is_continuous:
            if greedy:
                # Reference draws 100 samples and takes the max-log-prob one
                # (agent.py:809-812); the distribution mode is equivalent in the
                # scaled_normal case and deterministic, so we use it directly.
                actions = self.dists[0].mode
            else:
                actions = self.dists[0].rsample(key)
            raw = actions
            if self.actor.action_clip > 0.0:
                clip = jnp.full_like(actions, self.actor.action_clip)
                actions = actions * jax.lax.stop_gradient(clip / jnp.maximum(clip, jnp.abs(actions)))
            return [actions], [raw]
        keys = jax.random.split(key, len(self.dists))
        if greedy:
            modes = [d.mode for d in self.dists]
            return modes, modes
        samples = [d.rsample(k) for d, k in zip(self.dists, keys)]
        return samples, samples

    def log_prob(self, actions: List[jax.Array]) -> jax.Array:
        """Summed log-prob across heads; ``[...,]`` shaped."""
        return sum(d.log_prob(a) for d, a in zip(self.dists, actions))

    def entropy(self) -> jax.Array:
        return sum(d.entropy() for d in self.dists)


class RSSM:
    """Pure-functional RSSM composition (reference agent.py:344-500).

    Holds module definitions + static hyperparams; all state flows through args.
    `wm_params` is the world-model param dict with keys ``recurrent_model``,
    ``representation_model``, ``transition_model``, ``initial_recurrent_state``.
    """

    def __init__(
        self,
        recurrent_model: RecurrentModel,
        representation_model: MLPWithHead,
        transition_model: MLPWithHead,
        stochastic_size: int,
        discrete_size: int = 32,
        unimix: float = 0.01,
        learnable_initial_recurrent_state: bool = True,
        decoupled: bool = False,
        dynamic_scan_unroll: int = 1,
    ):
        self.recurrent_model = recurrent_model
        self.representation_model = representation_model
        self.transition_model = transition_model
        self.stochastic_size = stochastic_size
        self.discrete_size = discrete_size
        self.unimix = unimix
        self.learnable_initial_recurrent_state = learnable_initial_recurrent_state
        self.decoupled = decoupled
        # lax.scan unroll factor for the T-step dynamic scan: a step's matmuls have
        # B rows, so at every preset the step is bound by reading its kernels
        # ([5120, 12288] for XL's GRU), not by the MXU; unrolling lets XLA overlap
        # consecutive steps' HBM reads and MXU work
        self.dynamic_scan_unroll = int(dynamic_scan_unroll)

    @property
    def stoch_state_size(self) -> int:
        return self.stochastic_size * self.discrete_size

    def initial_states(self, wm_params: Dict[str, Any], batch_shape: Sequence[int]) -> Tuple[jax.Array, jax.Array]:
        """(initial recurrent state, initial posterior mode); reference agent.py:391-395."""
        raw = wm_params["initial_recurrent_state"]
        if not self.learnable_initial_recurrent_state:
            # fixed zeros buffer (reference registers a non-trainable buffer, agent.py:383-388)
            raw = jax.lax.stop_gradient(raw)
        init = jnp.tanh(raw)
        recurrent_state = jnp.broadcast_to(init, (*batch_shape, init.shape[-1]))
        logits, prior = self._transition(wm_params, recurrent_state, sample=False)
        return recurrent_state, prior.reshape(*batch_shape, -1)

    def _transition(
        self, wm_params, recurrent_out: jax.Array, key: Optional[jax.Array] = None, sample: bool = True
    ) -> Tuple[jax.Array, jax.Array]:
        logits = self.transition_model.apply(wm_params["transition_model"], recurrent_out)
        logits = uniform_mix(logits, self.discrete_size, self.unimix)
        return logits, compute_stochastic_state(logits, self.discrete_size, key, sample=sample)

    def _representation(
        self, wm_params, embedded_obs: jax.Array, key: jax.Array, recurrent_state: Optional[jax.Array] = None
    ) -> Tuple[jax.Array, jax.Array]:
        if self.decoupled:
            x = embedded_obs
        else:
            x = jnp.concatenate([recurrent_state, embedded_obs], axis=-1)
        logits = self.representation_model.apply(wm_params["representation_model"], x)
        logits = uniform_mix(logits, self.discrete_size, self.unimix)
        return logits, compute_stochastic_state(logits, self.discrete_size, key)

    def _recurrent(self, wm_params, posterior_flat: jax.Array, action: jax.Array, recurrent_state: jax.Array):
        x = jnp.concatenate([posterior_flat, action], axis=-1)
        return self.recurrent_model.apply(wm_params["recurrent_model"], x, recurrent_state)

    def dynamic_step(
        self,
        wm_params,
        posterior_flat: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        embedded_obs: jax.Array,
        is_first: jax.Array,
        key: jax.Array,
        initial_states: Optional[Tuple[jax.Array, jax.Array]] = None,
    ):
        """One step of dynamic learning (reference agent.py:396-435).

        ``initial_states`` is what `initial_states` returns for this batch; a
        scan computes it once and passes it to every step."""
        k_prior, k_post = jax.random.split(key)
        action = (1 - is_first) * action
        if initial_states is None:
            initial_states = self.initial_states(wm_params, recurrent_state.shape[:-1])
        init_rec, init_post = initial_states
        recurrent_state = (1 - is_first) * recurrent_state + is_first * init_rec
        posterior_flat = (1 - is_first) * posterior_flat + is_first * init_post
        recurrent_state = self._recurrent(wm_params, posterior_flat, action, recurrent_state)
        prior_logits, prior = self._transition(wm_params, recurrent_state, k_prior)
        posterior_logits, posterior = self._representation(
            wm_params, embedded_obs, k_post, recurrent_state=recurrent_state
        )
        return recurrent_state, posterior, prior, posterior_logits, prior_logits

    def _scan_taps(self, wm_params, names: Sequence[str], T: int, B: int):
        """The taps (`kernel_taps`) of the models ``names``, each of which the
        step of a scan of T steps at batch B applies ONCE: they go into the
        scan's ``xs``, and `_with_taps` puts a step's slice beside the params."""
        return {name: kernel_taps(wm_params[name]["params"], (T, B), getattr(self, name).dtype) for name in names}

    @staticmethod
    def _with_taps(wm_params, taps_t):
        return {**wm_params, **{name: {**wm_params[name], TAPS: taps} for name, taps in taps_t.items()}}

    def dynamic_scan(
        self,
        wm_params,
        embedded_obs: jax.Array,  # [T, B, E]
        actions: jax.Array,  # [T, B, A] (already shifted: a_{t-1} enters step t)
        is_first: jax.Array,  # [T, B, 1]
        key: jax.Array,
    ):
        """lax.scan over the sequence dim: the hot loop of world-model learning.

        **What leaves the scan stacked, and why.** The step closes over
        ``wm_params``, and the transpose of a ``lax.scan`` sums the cotangent of
        what its step closes over in the backward scan's carry. For a dense
        kernel that is a read and a write of the whole float32 kernel in every
        step, to add a product of B rows: at XL's GRU kernel (``[5120, 12288]``,
        252 MB) 0.5 GB a step, 64 times, where the same gradient as one
        contraction over ``[T, B]`` writes it once. So every dense kernel of the
        step (GRU, its input projection, the representation and transition
        trunks and heads: each is a `TapDot`) enters its product under
        ``stop_gradient``, and the backward scan emits, stacked over T, that
        product's input ``[B, in]`` and the cotangent at its output ``[B, out]``
        as the cotangent of per-step zeros (`kernel_taps`), whose own backward
        is the contraction. LayerNorm scales, biases and
        ``initial_recurrent_state`` are kilobytes and stay in the carry. The
        learned initial state is computed once, before the scan, for the same
        reason: inside the step it would apply the transition model a second
        time with the same tap.
        """
        T, B = embedded_obs.shape[0], embedded_obs.shape[1]
        keys = jax.random.split(key, T)
        init_rec = jnp.zeros((B, self.recurrent_model.recurrent_state_size), dtype=embedded_obs.dtype)
        init_post = jnp.zeros((B, self.stoch_state_size), dtype=embedded_obs.dtype)
        initial_states = self.initial_states(wm_params, (B,))

        if self.decoupled:
            # representation is independent of the recurrent state: batch it over [T,B]
            post_keys = jax.random.split(jax.random.fold_in(key, 1), T)

            def rep(embedded, k):
                return self._representation(wm_params, embedded, k)

            posteriors_logits, posteriors = jax.vmap(rep)(embedded_obs, post_keys)
            posteriors_flat = posteriors.reshape(T, B, -1)
            prev_posts = jnp.concatenate([jnp.zeros_like(posteriors_flat[:1]), posteriors_flat[:-1]], axis=0)
            taps = self._scan_taps(wm_params, ("recurrent_model", "transition_model"), T, B)

            def step(carry, xs):
                recurrent_state = carry
                prev_post, action, is_f, k, taps_t = xs
                params_t = self._with_taps(wm_params, taps_t)
                action = (1 - is_f) * action
                init_r, init_p = initial_states
                recurrent_state = (1 - is_f) * recurrent_state + is_f * init_r
                prev_post = (1 - is_f) * prev_post + is_f * init_p
                recurrent_state = self._recurrent(params_t, prev_post, action, recurrent_state)
                prior_logits, _ = self._transition(params_t, recurrent_state, k)
                return recurrent_state, (recurrent_state, prior_logits)

            _, (recurrent_states, priors_logits) = jax.lax.scan(
                step, init_rec, (prev_posts, actions, is_first, keys, taps), unroll=self.dynamic_scan_unroll
            )
            # logits leave flat [T,B,S*D]; expose factorized [T,B,S,D] (the shape the
            # KL-balance loss and entropy metrics expect, reference loss.py:45-70)
            priors_logits = priors_logits.reshape(T, B, self.stochastic_size, self.discrete_size)
            posteriors_logits = posteriors_logits.reshape(T, B, self.stochastic_size, self.discrete_size)
            return recurrent_states, posteriors, priors_logits, posteriors_logits

        taps = self._scan_taps(wm_params, ("recurrent_model", "representation_model", "transition_model"), T, B)

        def step(carry, xs):
            recurrent_state, posterior_flat = carry
            action, embedded, is_f, k, taps_t = xs
            params_t = self._with_taps(wm_params, taps_t)
            recurrent_state, posterior, prior, post_logits, prior_logits = self.dynamic_step(
                params_t, posterior_flat, recurrent_state, action, embedded, is_f, k, initial_states
            )
            new_carry = (recurrent_state, posterior.reshape(*posterior.shape[:-2], -1))
            return new_carry, (recurrent_state, posterior, post_logits, prior_logits)

        _, (recurrent_states, posteriors, posteriors_logits, priors_logits) = jax.lax.scan(
            step, (init_rec, init_post), (actions, embedded_obs, is_first, keys, taps), unroll=self.dynamic_scan_unroll
        )
        # factorized logits [T,B,S,D]: categorical_kl and the entropy metrics softmax
        # per-categorical over D, not over the flat S*D vector
        priors_logits = priors_logits.reshape(T, B, self.stochastic_size, self.discrete_size)
        posteriors_logits = posteriors_logits.reshape(T, B, self.stochastic_size, self.discrete_size)
        return recurrent_states, posteriors, priors_logits, posteriors_logits

    def imagination_step(self, wm_params, prior_flat: jax.Array, recurrent_state: jax.Array, actions: jax.Array, key):
        """One-step latent imagination (reference agent.py:482-498)."""
        recurrent_state = self._recurrent(wm_params, prior_flat, actions, recurrent_state)
        _, imagined_prior = self._transition(wm_params, recurrent_state, key)
        return imagined_prior.reshape(*prior_flat.shape), recurrent_state


class PlayerDV3:
    """Stateful host-side rollout policy over a single jitted step (reference agent.py:596-693).

    The per-step device work (encode -> recurrent -> representation -> actor) is one
    compiled XLA program; the recurrent/stochastic/action state lives on device.
    """

    def __init__(
        self,
        encoder: MultiEncoderDV3,
        rssm: RSSM,
        actor: Actor,
        actions_dim: Sequence[int],
        num_envs: int,
        stochastic_size: int,
        recurrent_state_size: int,
        discrete_size: int = 32,
        actor_type: Optional[str] = None,
    ):
        self.encoder = encoder
        self.rssm = rssm
        self.actor = actor
        self.actions_dim = tuple(actions_dim)
        self.num_envs = num_envs
        self.stochastic_size = stochastic_size
        self.recurrent_state_size = recurrent_state_size
        self.discrete_size = discrete_size
        self.actor_type = actor_type
        # filled by build_agent
        self.wm_params: Any = None
        self.actor_params: Any = None
        self._step = jax_compile.guarded_jit(
            self._raw_step, name="dv3.step", static_argnames=("greedy",)
        )
        self._packed_step_fns: Dict[Any, Any] = {}

    def _actor_step(self, actor_params, latent, key, greedy: bool = False, mask=None):
        """Sample actions from the latent; subclasses override to change how the
        actor is queried (e.g. PonderNet inference-mode halting in PlayerDAP);
        mask consumption is the actor's own concern (Actor.sample)."""
        pre_dist = self.actor.apply(actor_params, latent)
        return self.actor.sample(pre_dist, key, greedy=greedy, mask=mask)

    def _raw_step(self, wm_params, actor_params, state, obs, key, greedy: bool = False, mask=None):
        recurrent_state, stochastic_state, actions = state
        k_rep, k_act = jax.random.split(key)
        embedded = self.encoder.apply(wm_params["encoder"], obs)
        recurrent_state = self.rssm._recurrent(wm_params, stochastic_state, actions, recurrent_state)
        if self.rssm.decoupled:
            _, stoch = self.rssm._representation(wm_params, embedded, k_rep)
        else:
            _, stoch = self.rssm._representation(wm_params, embedded, k_rep, recurrent_state=recurrent_state)
        stochastic_state = stoch.reshape(*stoch.shape[:-2], self.stochastic_size * self.discrete_size)
        latent = jnp.concatenate([stochastic_state, recurrent_state], axis=-1)
        actions_list = host_float32(self._actor_step(actor_params, latent, k_act, greedy=greedy, mask=mask))
        actions = jnp.concatenate(actions_list, axis=-1)
        return tuple(actions_list), (recurrent_state, stochastic_state, actions)

    def init_states(self, reset_envs: Optional[Sequence[int]] = None) -> None:
        if reset_envs is None or len(reset_envs) == 0:
            actions = jnp.zeros((1, self.num_envs, int(np.sum(self.actions_dim))), dtype=jnp.float32)
            recurrent_state, stoch = self.rssm.initial_states(self.wm_params, (1, self.num_envs))
            self.state = (recurrent_state, stoch.reshape(1, self.num_envs, -1), actions)
        else:
            recurrent_state, stochastic_state, actions = self.state
            reset = np.zeros((self.num_envs,), dtype=bool)
            reset[np.asarray(reset_envs)] = True
            mask = jnp.asarray(reset)[None, :, None]
            init_rec, init_stoch = self.rssm.initial_states(self.wm_params, (1, self.num_envs))
            self.state = (
                jnp.where(mask, init_rec, recurrent_state),
                jnp.where(mask, init_stoch.reshape(1, self.num_envs, -1), stochastic_state),
                jnp.where(mask, 0.0, actions),
            )

    def get_actions(self, obs: Dict[str, jax.Array], key: jax.Array, greedy: bool = False, mask=None):
        # getattr: custom actors (e.g. PonderActor) aren't Actor subclasses
        if not getattr(self.actor, "uses_action_mask", False):
            mask = None  # avoids re-tracing _step on mask presence for mask-free actors
        actions_list, self.state = self._step(
            self.wm_params, self.actor_params, self.state, obs, key, greedy=greedy, mask=mask
        )
        return actions_list

    def get_actions_packed(self, codec, packed: jax.Array, key: jax.Array, greedy: bool = False):
        """Like get_actions but fed by ONE packed host->device transfer (see
        core/pipeline.PackedObsCodec): unpack + normalize + the ``mask_*``-key
        action-mask extraction all run in-graph."""
        fn = self.packed_step_fn(codec, greedy=greedy)
        actions_list, self.state = fn(self.wm_params, self.actor_params, self.state, packed, key)
        return actions_list

    def packed_step_fn(self, codec, greedy: bool = False):
        """The guarded jitted packed-step entry point for ``codec`` (exposed so
        the train loop can register its AOT warmup before the rollout starts).
        greedy/mask-usage close over the trace — no static args, AOT-friendly."""
        use_mask = bool(getattr(self.actor, "uses_action_mask", False))
        cache_key = (codec.signature, bool(greedy), use_mask)
        fn = self._packed_step_fns.get(cache_key)
        if fn is None:

            def _packed(wm_params, actor_params, state, packed, key):
                obs = codec.decode_obs(packed)
                mask = None
                if use_mask:
                    mask = {k: v for k, v in obs.items() if k.startswith("mask")} or None
                return self._raw_step(wm_params, actor_params, state, obs, key, greedy=greedy, mask=mask)

            fn = jax_compile.guarded_jit(_packed, name="dv3.step_packed")
            self._packed_step_fns[cache_key] = fn
        return fn


class DV3Modules(NamedTuple):
    """Static module definitions shared by the train step and the player."""

    encoder: MultiEncoderDV3
    rssm: RSSM
    observation_model: MultiDecoderDV3
    reward_model: MLPWithHead
    continue_model: MLPWithHead
    actor: Actor
    critic: MLPWithHead


def _ln_enabled(ln_cfg: Dict[str, Any]) -> Tuple[bool, float]:
    """Parse a reference-style layer_norm config {cls: ..., kw: {eps}} to (enabled, eps)."""
    if ln_cfg is None:
        return True, 1e-3
    cls = str(ln_cfg.get("cls", "LayerNorm"))
    enabled = not cls.rsplit(".", 1)[-1].lower().startswith("identity")
    eps = float(ln_cfg.get("kw", {}).get("eps", 1e-3))
    return enabled, eps


@jax_compile.setup_phase("build_agent")
def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space: gymnasium.spaces.Dict,
    world_model_state: Optional[Dict[str, Any]] = None,
    actor_state: Optional[Dict[str, Any]] = None,
    critic_state: Optional[Dict[str, Any]] = None,
    target_critic_state: Optional[Dict[str, Any]] = None,
    build_actor: bool = True,
) -> Tuple[DV3Modules, Dict[str, Any], PlayerDV3]:
    """Build module defs + init params (reference agent.py:935-1260).

    Returns (modules, params, player) where params is a dict with keys
    ``world_model``, ``actor``, ``critic``, ``target_critic``. With
    ``build_actor=False`` the actor and player are skipped (``None`` in the
    results) — for callers that supply their own actor (e.g. dream_and_ponder).
    """
    world_model_cfg = cfg.algo.world_model
    actor_cfg = cfg.algo.actor
    critic_cfg = cfg.algo.critic
    # a saved run's config still carries `kernels: off`, which yaml reads as False (eval, serve, resume)
    kernels = world_model_cfg.get("kernels")
    if str(kernels).lower() not in ("none", "false", "off"):
        raise ValueError(
            "algo.world_model.kernels is gone: the fused RSSM step was removed and the "
            f"flax scan is the one implementation; drop the key (got algo.world_model.kernels={kernels!r})"
        )

    recurrent_state_size = int(world_model_cfg.recurrent_model.recurrent_state_size)
    stochastic_size = int(world_model_cfg.stochastic_size) * int(world_model_cfg.discrete_size)
    latent_state_size = stochastic_size + recurrent_state_size
    compute_dtype = runtime.compute_dtype
    param_dtype = jnp.float32

    cnn_stages = int(np.log2(cfg.env.screen_size) - np.log2(4))
    cnn_ln, cnn_eps = _ln_enabled(world_model_cfg.encoder.get("cnn_layer_norm"))
    mlp_ln, mlp_eps = _ln_enabled(world_model_cfg.encoder.get("mlp_layer_norm"))
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)

    cnn_encoder = (
        CNNEncoder(
            keys=cnn_keys,
            input_channels=[int(np.prod(obs_space[k].shape[:-2])) for k in cnn_keys],
            image_size=tuple(obs_space[cnn_keys[0]].shape[-2:]),
            channels_multiplier=int(world_model_cfg.encoder.cnn_channels_multiplier),
            layer_norm=cnn_ln,
            layer_norm_eps=cnn_eps,
            activation=world_model_cfg.encoder.cnn_act,
            stages=cnn_stages,
            dtype=compute_dtype,
            param_dtype=param_dtype,
        )
        if len(cnn_keys) > 0
        else None
    )
    mlp_encoder = (
        MLPEncoder(
            keys=mlp_keys,
            input_dims=[int(obs_space[k].shape[0]) for k in mlp_keys],
            mlp_layers=int(world_model_cfg.encoder.mlp_layers),
            dense_units=int(world_model_cfg.encoder.dense_units),
            layer_norm=mlp_ln,
            layer_norm_eps=mlp_eps,
            activation=world_model_cfg.encoder.dense_act,
            dtype=compute_dtype,
            param_dtype=param_dtype,
        )
        if len(mlp_keys) > 0
        else None
    )
    encoder = MultiEncoderDV3(cnn_encoder, mlp_encoder)

    rec_ln, rec_eps = _ln_enabled(world_model_cfg.recurrent_model.get("layer_norm"))
    recurrent_model = RecurrentModel(
        input_size=int(sum(actions_dim) + stochastic_size),
        recurrent_state_size=recurrent_state_size,
        dense_units=int(world_model_cfg.recurrent_model.dense_units),
        layer_norm=rec_ln,
        layer_norm_eps=rec_eps,
        dtype=compute_dtype,
        param_dtype=param_dtype,
    )
    decoupled = bool(world_model_cfg.get("decoupled_rssm", False))
    repr_input = encoder.output_dim + (0 if decoupled else recurrent_state_size)
    repr_ln, repr_eps = _ln_enabled(world_model_cfg.representation_model.get("layer_norm"))
    representation_model = MLPWithHead(
        input_dim=repr_input,
        hidden_sizes=[int(world_model_cfg.representation_model.hidden_size)],
        output_dim=stochastic_size,
        activation=world_model_cfg.representation_model.dense_act,
        layer_norm=repr_ln,
        layer_norm_eps=repr_eps,
        head_init_scale=1.0 if cfg.algo.hafner_initialization else -1.0,
        dtype=compute_dtype,
        param_dtype=param_dtype,
    )
    trans_ln, trans_eps = _ln_enabled(world_model_cfg.transition_model.get("layer_norm"))
    transition_model = MLPWithHead(
        input_dim=recurrent_state_size,
        hidden_sizes=[int(world_model_cfg.transition_model.hidden_size)],
        output_dim=stochastic_size,
        activation=world_model_cfg.transition_model.dense_act,
        layer_norm=trans_ln,
        layer_norm_eps=trans_eps,
        head_init_scale=1.0 if cfg.algo.hafner_initialization else -1.0,
        dtype=compute_dtype,
        param_dtype=param_dtype,
    )
    rssm = RSSM(
        recurrent_model=recurrent_model,
        representation_model=representation_model,
        transition_model=transition_model,
        stochastic_size=int(world_model_cfg.stochastic_size),
        discrete_size=int(world_model_cfg.discrete_size),
        unimix=float(cfg.algo.unimix),
        learnable_initial_recurrent_state=bool(world_model_cfg.get("learnable_initial_recurrent_state", True)),
        decoupled=decoupled,
        dynamic_scan_unroll=int(world_model_cfg.get("dynamic_scan_unroll", 1)),
    )

    cnn_keys_dec = list(cfg.algo.cnn_keys.decoder)
    mlp_keys_dec = list(cfg.algo.mlp_keys.decoder)
    obs_cnn_ln, obs_cnn_eps = _ln_enabled(world_model_cfg.observation_model.get("cnn_layer_norm"))
    obs_mlp_ln, obs_mlp_eps = _ln_enabled(world_model_cfg.observation_model.get("mlp_layer_norm"))
    cnn_decoder = (
        CNNDecoder(
            keys=cnn_keys_dec,
            output_channels=[int(np.prod(obs_space[k].shape[:-2])) for k in cnn_keys_dec],
            channels_multiplier=int(world_model_cfg.observation_model.cnn_channels_multiplier),
            cnn_encoder_output_dim=cnn_encoder.output_dim,
            image_size=tuple(obs_space[cnn_keys_dec[0]].shape[-2:]),
            layer_norm=obs_cnn_ln,
            layer_norm_eps=obs_cnn_eps,
            activation=world_model_cfg.observation_model.cnn_act,
            stages=cnn_stages,
            dtype=compute_dtype,
            param_dtype=param_dtype,
        )
        if len(cnn_keys_dec) > 0
        else None
    )
    mlp_decoder = (
        MLPDecoder(
            keys=mlp_keys_dec,
            output_dims=[int(obs_space[k].shape[0]) for k in mlp_keys_dec],
            mlp_layers=int(world_model_cfg.observation_model.mlp_layers),
            dense_units=int(world_model_cfg.observation_model.dense_units),
            layer_norm=obs_mlp_ln,
            layer_norm_eps=obs_mlp_eps,
            activation=world_model_cfg.observation_model.dense_act,
            dtype=compute_dtype,
            param_dtype=param_dtype,
        )
        if len(mlp_keys_dec) > 0
        else None
    )
    observation_model = MultiDecoderDV3(cnn_decoder, mlp_decoder)

    rew_ln, rew_eps = _ln_enabled(world_model_cfg.reward_model.get("layer_norm"))
    reward_model = MLPWithHead(
        input_dim=latent_state_size,
        hidden_sizes=[int(world_model_cfg.reward_model.dense_units)] * int(world_model_cfg.reward_model.mlp_layers),
        output_dim=int(world_model_cfg.reward_model.bins),
        activation=world_model_cfg.reward_model.dense_act,
        layer_norm=rew_ln,
        layer_norm_eps=rew_eps,
        head_init_scale=0.0 if cfg.algo.hafner_initialization else -1.0,
        dtype=compute_dtype,
        param_dtype=param_dtype,
    )
    cont_ln, cont_eps = _ln_enabled(world_model_cfg.discount_model.get("layer_norm"))
    continue_model = MLPWithHead(
        input_dim=latent_state_size,
        hidden_sizes=[int(world_model_cfg.discount_model.dense_units)]
        * int(world_model_cfg.discount_model.mlp_layers),
        output_dim=1,
        activation=world_model_cfg.discount_model.dense_act,
        layer_norm=cont_ln,
        layer_norm_eps=cont_eps,
        head_init_scale=1.0 if cfg.algo.hafner_initialization else -1.0,
        dtype=compute_dtype,
        param_dtype=param_dtype,
    )

    actor_ln, actor_eps = _ln_enabled(actor_cfg.get("layer_norm"))
    # Config-selected actor class (reference uses hydra.utils.get_class on
    # cfg.algo.actor.cls, agent.py:1184): MinedojoActor adds rollout-time masking
    actor_cls = resolve_actor_cls(actor_cfg.get("cls"), Actor, MinedojoActor)
    actor = None if not build_actor else actor_cls(
        latent_state_size=latent_state_size,
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        distribution=cfg.distribution.get("type", "auto"),
        init_std=float(actor_cfg.init_std),
        min_std=float(actor_cfg.min_std),
        max_std=float(actor_cfg.get("max_std", 1.0)),
        dense_units=int(actor_cfg.dense_units),
        mlp_layers=int(actor_cfg.mlp_layers),
        layer_norm=actor_ln,
        layer_norm_eps=actor_eps,
        activation=actor_cfg.dense_act,
        unimix=float(cfg.algo.unimix),
        action_clip=float(actor_cfg.get("action_clip", 1.0)),
        dtype=compute_dtype,
        param_dtype=param_dtype,
    )
    critic_ln, critic_eps = _ln_enabled(critic_cfg.get("layer_norm"))
    critic = MLPWithHead(
        input_dim=latent_state_size,
        hidden_sizes=[int(critic_cfg.dense_units)] * int(critic_cfg.mlp_layers),
        output_dim=int(critic_cfg.bins),
        activation=critic_cfg.dense_act,
        layer_norm=critic_ln,
        layer_norm_eps=critic_eps,
        head_init_scale=0.0 if cfg.algo.hafner_initialization else -1.0,
        dtype=compute_dtype,
        param_dtype=param_dtype,
    )

    # ---- init params
    with jax_compile.setup_phase("build_agent.init"):
        key = jax.random.PRNGKey(cfg.seed)
        keys = jax.random.split(key, 10)
        dummy_obs: Dict[str, jax.Array] = {}
        for k in cnn_keys:
            dummy_obs[k] = jnp.zeros((1, int(np.prod(obs_space[k].shape[:-2])), *obs_space[k].shape[-2:]))
        for k in mlp_keys:
            dummy_obs[k] = jnp.zeros((1, int(obs_space[k].shape[0])))
        wm_params: Dict[str, Any] = {}
        wm_params["encoder"] = encoder.init(keys[0], dummy_obs)
        wm_params["recurrent_model"] = recurrent_model.init(
            keys[1], jnp.zeros((1, int(sum(actions_dim)) + stochastic_size)), jnp.zeros((1, recurrent_state_size))
        )
        wm_params["representation_model"] = representation_model.init(keys[2], jnp.zeros((1, repr_input)))
        wm_params["transition_model"] = transition_model.init(keys[3], jnp.zeros((1, recurrent_state_size)))
        wm_params["observation_model"] = observation_model.init(keys[4], jnp.zeros((1, latent_state_size)))
        wm_params["reward_model"] = reward_model.init(keys[5], jnp.zeros((1, latent_state_size)))
        wm_params["continue_model"] = continue_model.init(keys[6], jnp.zeros((1, latent_state_size)))
        wm_params["initial_recurrent_state"] = jnp.zeros((recurrent_state_size,), dtype=jnp.float32)
        actor_params = actor.init(keys[7], jnp.zeros((1, latent_state_size))) if build_actor else None
        critic_params = critic.init(keys[8], jnp.zeros((1, latent_state_size)))

        if world_model_state:
            wm_params = jax.tree_util.tree_map(jnp.asarray, world_model_state)
        if actor_state and build_actor:
            actor_params = jax.tree_util.tree_map(jnp.asarray, actor_state)
        if critic_state:
            critic_params = jax.tree_util.tree_map(jnp.asarray, critic_state)
        target_critic_params = (
            jax.tree_util.tree_map(jnp.asarray, target_critic_state)
            if target_critic_state
            else copy.deepcopy(critic_params)
        )

    modules = DV3Modules(
        encoder=encoder,
        rssm=rssm,
        observation_model=observation_model,
        reward_model=reward_model,
        continue_model=continue_model,
        actor=actor,
        critic=critic,
    )
    params = {
        "world_model": wm_params,
        "actor": actor_params,
        "critic": critic_params,
        "target_critic": target_critic_params,
    }

    player = None
    if build_actor:
        player = PlayerDV3(
            encoder=encoder,
            rssm=rssm,
            actor=actor,
            actions_dim=actions_dim,
            num_envs=cfg.env.num_envs,
            stochastic_size=int(world_model_cfg.stochastic_size),
            recurrent_state_size=recurrent_state_size,
            discrete_size=int(world_model_cfg.discrete_size),
        )
        player.wm_params = wm_params
        player.actor_params = actor_params
    return modules, params, player
