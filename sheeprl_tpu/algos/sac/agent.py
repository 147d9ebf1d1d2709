"""SAC agent (flax): tanh-squashed gaussian actor + vmapped critic ensemble.

Parity with reference sheeprl/algos/sac/agent.py (SACActor :57, SACCritic :20,
SACAgent :145, SACPlayer :270, build_agent :317). TPU-first choice: the N critics are
ONE module with a stacked (vmapped) parameter ensemble — N Q-forwards become one
batched matmul chain on the MXU instead of N sequential module calls.
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
from sheeprl_tpu.models.models import MLP
from sheeprl_tpu.utils.utils import host_float32

LOG_STD_MAX = 2
LOG_STD_MIN = -5


def action_scale_bias(low, high) -> Tuple[jax.Array, jax.Array]:
    """tanh-squash affine from box bounds, with non-finite bounds masked.

    ``(high-low)/2`` and ``(high+low)/2`` on an unbounded dim produce inf and
    inf-inf=NaN (a RuntimeWarning factory that would drown real NaN regressions
    in CI logs); an unbounded dim gets the identity map (scale 1, bias 0)
    instead — tanh already keeps the raw action finite.
    """
    low = np.asarray(low, dtype=np.float32)
    high = np.asarray(high, dtype=np.float32)
    bounded = np.isfinite(low) & np.isfinite(high)
    with np.errstate(invalid="ignore", over="ignore"):
        scale = np.where(bounded, (high - low) / 2.0, 1.0).astype(np.float32)
        bias = np.where(bounded, (high + low) / 2.0, 0.0).astype(np.float32)
    return jnp.asarray(scale), jnp.asarray(bias)


class SACActor(nn.Module):
    action_dim: int
    hidden_size: int = 256
    action_low: Any = -1.0
    action_high: Any = 1.0
    dtype: Any = jnp.float32

    @property
    def action_scale(self):
        return action_scale_bias(self.action_low, self.action_high)[0]

    @property
    def action_bias(self):
        return action_scale_bias(self.action_low, self.action_high)[1]

    @nn.compact
    def __call__(self, obs: jax.Array) -> Tuple[jax.Array, jax.Array]:
        x = MLP(input_dims=1, hidden_sizes=(self.hidden_size, self.hidden_size), dtype=self.dtype)(obs)
        mean = nn.Dense(self.action_dim, dtype=self.dtype)(x).astype(jnp.float32)
        log_std = nn.Dense(self.action_dim, dtype=self.dtype)(x).astype(jnp.float32)
        return mean, log_std


def actor_action_and_log_prob(mean: jax.Array, log_std: jax.Array, key, action_scale, action_bias):
    """tanh-squashed rsample + Eq. 26 log-prob (reference agent.py:111-144)."""
    std = jnp.exp(jnp.clip(log_std, LOG_STD_MIN, LOG_STD_MAX))
    x_t = mean + std * jax.random.normal(key, mean.shape, dtype=mean.dtype)
    y_t = jnp.tanh(x_t)
    action = y_t * action_scale + action_bias
    var = std**2
    log_prob = -((x_t - mean) ** 2) / (2 * var) - jnp.log(std) - 0.5 * jnp.log(2 * jnp.pi)
    log_prob = log_prob - jnp.log(action_scale * (1 - y_t**2) + 1e-6)
    return action, log_prob.sum(-1, keepdims=True)


def actor_greedy_action(mean: jax.Array, action_scale, action_bias) -> jax.Array:
    return jnp.tanh(mean) * action_scale + action_bias


class SACCritic(nn.Module):
    """Q(s, a) MLP; one instance is vmapped into the ensemble (reference :20-54)."""

    hidden_size: int = 256
    num_critics: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: jax.Array, action: jax.Array) -> jax.Array:
        x = jnp.concatenate([obs, action], axis=-1)
        return MLP(
            input_dims=1,
            output_dim=self.num_critics,
            hidden_sizes=(self.hidden_size, self.hidden_size),
            dtype=self.dtype,
        )(x).astype(jnp.float32)


class SACParams(NamedTuple):
    """Trainable state pytree (replaces the reference's SACAgent nn.Module :145)."""

    actor: Any
    critics: Any  # stacked ensemble params, leading axis = n critics
    target_critics: Any
    log_alpha: jax.Array


def init_sac_params(
    key: jax.Array,
    actor: SACActor,
    critic: SACCritic,
    n_critics: int,
    obs_dim: int,
    act_dim: int,
    alpha: float,
) -> SACParams:
    k_actor, k_crit = jax.random.split(key)
    actor_params = actor.init(k_actor, jnp.zeros((1, obs_dim)))
    crit_keys = jax.random.split(k_crit, n_critics)
    critics_params = jax.vmap(lambda k: critic.init(k, jnp.zeros((1, obs_dim)), jnp.zeros((1, act_dim))))(crit_keys)
    return SACParams(
        actor=actor_params,
        critics=critics_params,
        target_critics=jax.tree_util.tree_map(jnp.array, critics_params),
        log_alpha=jnp.log(jnp.asarray([alpha], dtype=jnp.float32)),
    )


def ensemble_q_values(critic: SACCritic, critics_params, obs: jax.Array, action: jax.Array) -> jax.Array:
    """All N Q-values in one vmapped call -> [batch, N]."""
    qs = jax.vmap(lambda p: critic.apply(p, obs, action))(critics_params)  # [N, B, 1]
    return jnp.moveaxis(qs[..., 0], 0, -1)


class SACPlayer:
    """Rollout/eval-side policy (reference SACPlayer :270)."""

    def __init__(self, actor: SACActor, actor_params, action_scale, action_bias):
        self.actor = actor
        self.params = actor_params
        self.action_scale = action_scale
        self.action_bias = action_bias

        def _act(params, obs, key):
            mean, log_std = actor.apply(params, obs)
            action, _ = actor_action_and_log_prob(mean, log_std, key, action_scale, action_bias)
            # host_float32: actions are stored f32 in the buffers (the dtype
            # contract of utils.host_float32)
            return host_float32(action)

        def _greedy(params, obs):
            mean, _ = actor.apply(params, obs)
            return host_float32(actor_greedy_action(mean, action_scale, action_bias))

        self._act = jax_compile.guarded_jit(_act, name="sac.act")
        self._greedy = jax_compile.guarded_jit(_greedy, name="sac.greedy")

    def get_actions(self, obs: jax.Array, key: Optional[jax.Array] = None, greedy: bool = False) -> jax.Array:
        if greedy:
            return self._greedy(self.params, obs)
        return self._act(self.params, obs, key)

    __call__ = get_actions


def build_agent(
    runtime,
    cfg,
    obs_space: gymnasium.spaces.Dict,
    action_space: gymnasium.spaces.Box,
    agent_state: Optional[Dict[str, Any]] = None,
):
    """Returns (actor, critic, params: SACParams, player). Reference: agent.py:317."""
    act_dim = prod(action_space.shape)
    obs_dim = sum(prod(obs_space[k].shape) for k in cfg.algo.mlp_keys.encoder)
    actor = SACActor(
        action_dim=act_dim,
        hidden_size=cfg.algo.actor.hidden_size,
        action_low=tuple(np.asarray(action_space.low, dtype=np.float32).tolist()),
        action_high=tuple(np.asarray(action_space.high, dtype=np.float32).tolist()),
        dtype=runtime.compute_dtype,
    )
    critic = SACCritic(hidden_size=cfg.algo.critic.hidden_size, num_critics=1, dtype=runtime.compute_dtype)
    params = init_sac_params(
        jax.random.PRNGKey(cfg.seed),
        actor,
        critic,
        cfg.algo.critic.n,
        obs_dim,
        act_dim,
        cfg.algo.alpha.alpha,
    )
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, SACParams(*agent_state) if isinstance(agent_state, (tuple, list)) else agent_state)
        if not isinstance(params, SACParams):
            params = SACParams(**params) if isinstance(params, dict) else params
    params = runtime.place_params(params)
    action_scale, action_bias = action_scale_bias(action_space.low, action_space.high)
    player = SACPlayer(actor, params.actor, action_scale, action_bias)
    return actor, critic, params, player
