"""SAC-AE agent: pixel SAC with a reconstruction autoencoder.

Parity with reference sheeprl/algos/sac_ae/agent.py — CNNEncoder (:26, 4x conv3x3
stride [2,1,1,1] + tanh/LayerNorm fc), MLPEncoder (:89), MLPDecoder (:122),
CNNDecoder (:153), SACAEQFunction (:204), SACAECritic (:226),
SACAEContinuousActor (:240, tanh-rescaled log-std), SACAEAgent (:321),
SACAEPlayer (:453), build_agent (:505).

JAX design note: the reference ties the actor-encoder conv weights to the critic
encoder (SAC-AE paper trick). Here there is ONE encoder param tree; the actor simply
applies it under ``stop_gradient`` (``detach_encoder_features`` in the reference) —
same semantics, no weight-tying machinery.
"""

from __future__ import annotations

from math import prod
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.algos.sac.agent import action_scale_bias, actor_action_and_log_prob
from sheeprl_tpu.models.models import CNN, MLP, DeCNN, LayerNorm
from sheeprl_tpu.utils.utils import host_float32

LOG_STD_MAX = 2
LOG_STD_MIN = -10


class SACAECNNEncoder(nn.Module):
    in_channels: int
    features_dim: int
    keys: Sequence[str]
    screen_size: int = 64
    cnn_channels_multiplier: int = 1
    dtype: Any = jnp.float32

    @property
    def conv_output_shape(self) -> Tuple[int, int, int]:
        # 4 convs k3: stride 2 then three stride 1 -> size = (s-1)//2 - 3 + 1 rules
        s = (self.screen_size - 3) // 2 + 1
        for _ in range(3):
            s = s - 3 + 1
        return (32 * self.cnn_channels_multiplier, s, s)

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array], detach_encoder_features: bool = False) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-3)
        ch = 32 * self.cnn_channels_multiplier
        x = CNN(
            input_channels=self.in_channels,
            hidden_channels=[ch, ch, ch, ch],
            layer_args=[
                {"kernel_size": 3, "stride": 2},
                {"kernel_size": 3, "stride": 1},
                {"kernel_size": 3, "stride": 1},
                {"kernel_size": 3, "stride": 1},
            ],
            dtype=self.dtype,
        )(x)
        x = x.reshape(x.shape[0], -1)
        if detach_encoder_features:
            x = jax.lax.stop_gradient(x)
        x = MLP(
            input_dims=1,
            hidden_sizes=(self.features_dim,),
            activation="tanh",
            layer_norm=True,
            dtype=self.dtype,
        )(x)
        return x.astype(jnp.float32)


class SACAEMLPEncoder(nn.Module):
    input_dim: int
    keys: Sequence[str]
    dense_units: int = 1024
    mlp_layers: int = 3
    dense_act: str = "relu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array], detach_encoder_features: bool = False) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        x = MLP(
            input_dims=self.input_dim,
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.dense_act,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(x)
        if detach_encoder_features:
            x = jax.lax.stop_gradient(x)
        return x.astype(jnp.float32)


class SACAEEncoder(nn.Module):
    """MultiEncoder with detach pass-through (reference MultiEncoder usage)."""

    cnn_encoder: Optional[nn.Module]
    mlp_encoder: Optional[nn.Module]

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array], detach_encoder_features: bool = False) -> jax.Array:
        outs = []
        if self.cnn_encoder is not None:
            outs.append(self.cnn_encoder(obs, detach_encoder_features))
        if self.mlp_encoder is not None:
            outs.append(self.mlp_encoder(obs, detach_encoder_features))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


class SACAECNNDecoder(nn.Module):
    conv_output_shape: Tuple[int, int, int]
    features_dim: int
    keys: Sequence[str]
    channels: Sequence[int]
    screen_size: int = 64
    cnn_channels_multiplier: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> Dict[str, jax.Array]:
        ch = 32 * self.cnn_channels_multiplier
        x = MLP(input_dims=1, hidden_sizes=(prod(self.conv_output_shape),), dtype=self.dtype)(x)
        x = x.reshape(-1, *self.conv_output_shape)
        x = DeCNN(
            input_channels=ch,
            hidden_channels=[ch, ch, ch],
            layer_args=[
                {"kernel_size": 3, "stride": 1},
                {"kernel_size": 3, "stride": 1},
                {"kernel_size": 3, "stride": 1},
            ],
            dtype=self.dtype,
        )(x)
        x = DeCNN(
            input_channels=ch,
            hidden_channels=[sum(self.channels)],
            layer_args=[{"kernel_size": 3, "stride": 2, "output_padding": 1}],
            activation=None,
            dtype=self.dtype,
        )(x).astype(jnp.float32)
        out: Dict[str, jax.Array] = {}
        start = 0
        for k, c in zip(self.keys, self.channels):
            out[k] = x[..., start : start + c, :, :]
            start += c
        return out


class SACAEMLPDecoder(nn.Module):
    input_dim: int
    output_dims: Sequence[int]
    keys: Sequence[str]
    dense_units: int = 1024
    mlp_layers: int = 3
    dense_act: str = "relu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> Dict[str, jax.Array]:
        x = MLP(
            input_dims=self.input_dim,
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.dense_act,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(x)
        return {
            k: nn.Dense(d, dtype=self.dtype)(x).astype(jnp.float32) for k, d in zip(self.keys, self.output_dims)
        }


class SACAEDecoder(nn.Module):
    cnn_decoder: Optional[nn.Module]
    mlp_decoder: Optional[nn.Module]

    @nn.compact
    def __call__(self, x: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(x))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(x))
        return out


class SACAEQFunction(nn.Module):
    hidden_size: int = 1024
    output_dim: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, features: jax.Array, action: jax.Array) -> jax.Array:
        x = jnp.concatenate([features, action], axis=-1)
        return MLP(
            input_dims=1,
            output_dim=self.output_dim,
            hidden_sizes=(self.hidden_size, self.hidden_size),
            dtype=self.dtype,
        )(x).astype(jnp.float32)


class SACAEActorHead(nn.Module):
    """Actor MLP over encoder features; tanh-rescaled log-std (reference :240-320)."""

    action_dim: int
    hidden_size: int = 1024
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, features: jax.Array) -> Tuple[jax.Array, jax.Array]:
        x = MLP(input_dims=1, hidden_sizes=(self.hidden_size, self.hidden_size), dtype=self.dtype)(features)
        mean = nn.Dense(self.action_dim, dtype=self.dtype)(x).astype(jnp.float32)
        log_std = nn.Dense(self.action_dim, dtype=self.dtype)(x).astype(jnp.float32)
        log_std = jnp.tanh(log_std)
        log_std = LOG_STD_MIN + 0.5 * (LOG_STD_MAX - LOG_STD_MIN) * (log_std + 1)
        return mean, log_std


class SACAEParams(NamedTuple):
    encoder: Any
    target_encoder: Any
    qfs: Any  # stacked ensemble
    target_qfs: Any
    actor: Any
    decoder: Any
    log_alpha: jax.Array


class SACAEPlayer:
    """Rollout/eval policy: encoder + actor head (reference SACAEPlayer :453)."""

    def __init__(self, encoder, actor_head, params: SACAEParams, action_scale, action_bias):
        self.encoder = encoder
        self.actor_head = actor_head
        self.encoder_params = params.encoder
        self.actor_params = params.actor
        self.action_scale = action_scale
        self.action_bias = action_bias

        def _act(enc_params, actor_params, obs, key):
            feats = encoder.apply(enc_params, obs)
            mean, log_std = actor_head.apply(actor_params, feats)
            action, _ = actor_action_and_log_prob(mean, log_std, key, action_scale, action_bias)
            # host_float32: actions are stored f32 in the buffers (the dtype
            # contract of utils.host_float32)
            return host_float32(action)

        def _greedy(enc_params, actor_params, obs):
            feats = encoder.apply(enc_params, obs)
            mean, _ = actor_head.apply(actor_params, feats)
            return host_float32(jnp.tanh(mean) * action_scale + action_bias)

        self._act = jax_compile.guarded_jit(_act, name="sac_ae.act")
        self._greedy = jax_compile.guarded_jit(_greedy, name="sac_ae.greedy")

    def get_actions(self, obs, key=None, greedy: bool = False):
        if greedy:
            return self._greedy(self.encoder_params, self.actor_params, obs)
        return self._act(self.encoder_params, self.actor_params, obs, key)

    __call__ = get_actions


def build_agent(
    runtime,
    cfg,
    obs_space: gymnasium.spaces.Dict,
    action_space: gymnasium.spaces.Box,
    agent_state: Optional[Any] = None,
):
    """Returns (modules dict, params: SACAEParams, player). Reference: agent.py:505."""
    act_dim = prod(action_space.shape)
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cnn_channels = [prod(obs_space[k].shape[:-2]) for k in cnn_keys]
    mlp_dims = [obs_space[k].shape[0] for k in mlp_keys]
    cnn_encoder = (
        SACAECNNEncoder(
            in_channels=sum(cnn_channels),
            features_dim=cfg.algo.encoder.features_dim,
            keys=tuple(cnn_keys),
            screen_size=cfg.env.screen_size,
            cnn_channels_multiplier=cfg.algo.encoder.cnn_channels_multiplier,
            dtype=runtime.compute_dtype,
        )
        if cnn_keys
        else None
    )
    mlp_encoder = (
        SACAEMLPEncoder(
            sum(mlp_dims),
            tuple(mlp_keys),
            cfg.algo.encoder.dense_units,
            cfg.algo.encoder.mlp_layers,
            cfg.algo.encoder.dense_act,
            cfg.algo.encoder.layer_norm,
            dtype=runtime.compute_dtype,
        )
        if mlp_keys
        else None
    )
    encoder = SACAEEncoder(cnn_encoder, mlp_encoder)
    features_dim = (cfg.algo.encoder.features_dim if cnn_keys else 0) + (
        cfg.algo.encoder.dense_units if mlp_keys else 0
    )
    cnn_decoder = (
        SACAECNNDecoder(
            cnn_encoder.conv_output_shape,
            features_dim=features_dim,
            keys=tuple(cfg.algo.cnn_keys.decoder),
            channels=tuple(cnn_channels),
            screen_size=cfg.env.screen_size,
            cnn_channels_multiplier=cfg.algo.decoder.cnn_channels_multiplier,
            dtype=runtime.compute_dtype,
        )
        if cfg.algo.cnn_keys.decoder
        else None
    )
    mlp_decoder = (
        SACAEMLPDecoder(
            features_dim,
            tuple(mlp_dims),
            tuple(cfg.algo.mlp_keys.decoder),
            cfg.algo.decoder.dense_units,
            cfg.algo.decoder.mlp_layers,
            cfg.algo.decoder.dense_act,
            cfg.algo.decoder.layer_norm,
            dtype=runtime.compute_dtype,
        )
        if cfg.algo.mlp_keys.decoder
        else None
    )
    decoder = SACAEDecoder(cnn_decoder, mlp_decoder)
    qf = SACAEQFunction(hidden_size=cfg.algo.critic.hidden_size, output_dim=1, dtype=runtime.compute_dtype)
    actor_head = SACAEActorHead(act_dim, cfg.algo.actor.hidden_size, dtype=runtime.compute_dtype)

    key = jax.random.PRNGKey(cfg.seed)
    k_enc, k_qf, k_actor, k_dec = jax.random.split(key, 4)
    sample_obs = {}
    for k in cnn_keys:
        shape = obs_space[k].shape
        sample_obs[k] = jnp.zeros((1, prod(shape[:-2]), *shape[-2:]), dtype=jnp.float32)
    for k in mlp_keys:
        sample_obs[k] = jnp.zeros((1, *obs_space[k].shape), dtype=jnp.float32)
    enc_params = encoder.init(k_enc, sample_obs)
    feats = encoder.apply(enc_params, sample_obs)
    qf_keys = jax.random.split(k_qf, cfg.algo.critic.n)
    qfs_params = jax.vmap(lambda kk: qf.init(kk, feats, jnp.zeros((1, act_dim))))(qf_keys)
    actor_params = actor_head.init(k_actor, feats)
    dec_params = decoder.init(k_dec, feats)
    params = SACAEParams(
        encoder=enc_params,
        target_encoder=jax.tree_util.tree_map(jnp.array, enc_params),
        qfs=qfs_params,
        target_qfs=jax.tree_util.tree_map(jnp.array, qfs_params),
        actor=actor_params,
        decoder=dec_params,
        log_alpha=jnp.log(jnp.asarray([cfg.algo.alpha.alpha], dtype=jnp.float32)),
    )
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
        if not isinstance(params, SACAEParams):
            params = SACAEParams(*params) if isinstance(params, (tuple, list)) else SACAEParams(**params)
    params = runtime.place_params(params)
    action_scale, action_bias = action_scale_bias(action_space.low, action_space.high)
    player = SACAEPlayer(encoder, actor_head, params, action_scale, action_bias)
    modules = {"encoder": encoder, "decoder": decoder, "qf": qf, "actor_head": actor_head}
    return modules, params, player
