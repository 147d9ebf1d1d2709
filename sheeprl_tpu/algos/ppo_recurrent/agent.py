"""Recurrent PPO agent (reference sheeprl/algos/ppo_recurrent/agent.py).

RecurrentModel (:18): optional pre-MLP -> single-layer LSTM -> optional post-MLP.
RecurrentPPOAgent (:83): encoder + rnn(features ++ prev_actions) -> actor heads +
critic. TPU design: the LSTM is a flax LSTMCell scanned with ``lax.scan`` over time;
padded timesteps freeze the carry via the mask (replaces torch pack_padded_sequence).
"""

from __future__ import annotations

from math import prod
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.algos.ppo.agent import CNNEncoder, MLPEncoder, evaluate_actions, sample_actions
from sheeprl_tpu.models.models import MLP, MultiEncoder
from sheeprl_tpu.utils.utils import host_float32


class RecurrentModel(nn.Module):
    lstm_hidden_size: int
    pre_rnn_mlp_cfg: Dict[str, Any]
    post_rnn_mlp_cfg: Dict[str, Any]
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: jax.Array,  # [T, B, D]
        states: Tuple[jax.Array, jax.Array],  # (hx, cx) each [B, H]
        mask: Optional[jax.Array] = None,  # [T, B, 1]
    ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
        if self.pre_rnn_mlp_cfg["apply"]:
            x = MLP(
                input_dims=1,
                hidden_sizes=[self.pre_rnn_mlp_cfg["dense_units"]],
                activation=self.pre_rnn_mlp_cfg["activation"],
                layer_norm=self.pre_rnn_mlp_cfg["layer_norm"],
                dtype=self.dtype,
            )(x)
        cell = nn.OptimizedLSTMCell(self.lstm_hidden_size, dtype=self.dtype, param_dtype=jnp.float32)
        rnn = nn.RNN(cell, time_major=True, return_carry=True)
        hx, cx = states
        carry0 = (cx.astype(self.dtype), hx.astype(self.dtype))
        # seq_lengths freezes the carry past each sequence's end — the in-graph
        # analogue of torch pack_padded_sequence (reference agent.py:74-80).
        seq_lengths = None
        if mask is not None:
            seq_lengths = mask[..., 0].sum(axis=0).astype(jnp.int32)
        (cx_f, hx_f), out = rnn(x.astype(self.dtype), initial_carry=carry0, seq_lengths=seq_lengths)
        if mask is not None:
            out = out * mask.astype(out.dtype)
        if self.post_rnn_mlp_cfg["apply"]:
            out = MLP(
                input_dims=1,
                hidden_sizes=[self.post_rnn_mlp_cfg["dense_units"]],
                activation=self.post_rnn_mlp_cfg["activation"],
                layer_norm=self.post_rnn_mlp_cfg["layer_norm"],
                dtype=self.dtype,
            )(out)
        return out.astype(jnp.float32), (hx_f.astype(jnp.float32), cx_f.astype(jnp.float32))


class RecurrentPPOAgent(nn.Module):
    """Encoder + RNN(features ++ prev_actions) + actor/critic heads (reference :83)."""

    actions_dim: Sequence[int]
    is_continuous: bool
    distribution: str
    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_input_channels: int
    mlp_input_dim: int
    screen_size: int
    encoder_cfg: Dict[str, Any]
    rnn_cfg: Dict[str, Any]
    actor_cfg: Dict[str, Any]
    critic_cfg: Dict[str, Any]
    dtype: Any = jnp.float32

    # ----- the sequence-policy seam of ppo_recurrent (token_agent.TokenPolicy is the other side of it)
    starts_at_reset = False  # the state is stored per step, so a training sequence may start anywhere

    @property
    def rnn_hidden_size(self) -> int:
        return self.rnn_cfg["lstm"]["hidden_size"]

    @property
    def action_width(self) -> int:
        """Numbers a stored action takes: the concatenated one-hot / continuous vector."""
        return sum(self.actions_dim)

    def loss_mask(self, batch: Dict[str, jax.Array]) -> jax.Array:
        return batch["mask"]

    def evaluate(self, params, batch: Dict[str, jax.Array], norm_obs: Dict[str, jax.Array]):
        """(log-prob, entropy, value) of the batch's actions, each [T, B, 1], from the
        stored state of each sequence's first step; no extra metrics."""
        actions = (
            jnp.split(batch["actions"], np.cumsum(self.actions_dim)[:-1].tolist(), axis=-1)
            if len(self.actions_dim) > 1
            else [batch["actions"]]
        )
        actor_outs, values, _ = self.apply(
            params, norm_obs, batch["prev_actions"], (batch["prev_hx"][0], batch["prev_cx"][0]), batch["mask"]
        )
        new_logprobs, entropy = evaluate_actions(actor_outs, actions, self.is_continuous, self.distribution)
        return new_logprobs, entropy, values, {}

    def setup(self) -> None:
        cnn_encoder = (
            CNNEncoder(
                self.cnn_input_channels,
                self.encoder_cfg["cnn_features_dim"],
                self.screen_size,
                self.cnn_keys,
                dtype=self.dtype,
            )
            if len(self.cnn_keys) > 0
            else None
        )
        mlp_encoder = (
            MLPEncoder(
                self.mlp_input_dim,
                self.encoder_cfg["mlp_features_dim"],
                self.mlp_keys,
                self.encoder_cfg["dense_units"],
                self.encoder_cfg["mlp_layers"],
                self.encoder_cfg["dense_act"],
                self.encoder_cfg["layer_norm"],
                dtype=self.dtype,
            )
            if len(self.mlp_keys) > 0
            else None
        )
        self.feature_extractor = MultiEncoder(cnn_encoder, mlp_encoder)
        self.rnn = RecurrentModel(
            lstm_hidden_size=self.rnn_cfg["lstm"]["hidden_size"],
            pre_rnn_mlp_cfg=dict(self.rnn_cfg["pre_rnn_mlp"]),
            post_rnn_mlp_cfg=dict(self.rnn_cfg["post_rnn_mlp"]),
            dtype=self.dtype,
        )
        self.critic = MLP(
            input_dims=1,
            output_dim=1,
            hidden_sizes=[self.critic_cfg["dense_units"]] * self.critic_cfg["mlp_layers"],
            activation=self.critic_cfg["dense_act"],
            layer_norm=self.critic_cfg["layer_norm"],
        )
        self.actor_backbone = MLP(
            input_dims=1,
            output_dim=None,
            hidden_sizes=[self.actor_cfg["dense_units"]] * self.actor_cfg["mlp_layers"],
            activation=self.actor_cfg["dense_act"],
            layer_norm=self.actor_cfg["layer_norm"],
        )
        if self.is_continuous:
            self.actor_heads = [nn.Dense(sum(self.actions_dim) * 2)]
        else:
            self.actor_heads = [nn.Dense(d) for d in self.actions_dim]

    def __call__(
        self,
        obs: Dict[str, jax.Array],  # values [T, B, ...]
        prev_actions: jax.Array,  # [T, B, sum(actions_dim)]
        prev_states: Tuple[jax.Array, jax.Array],  # (hx, cx) each [B, H]
        mask: Optional[jax.Array] = None,  # [T, B, 1]
    ) -> Tuple[List[jax.Array], jax.Array, jax.Array, Tuple[jax.Array, jax.Array]]:
        """Returns (actor_outs [T,B,*], values [T,B,1], rnn_out, new_states)."""
        feats = self.feature_extractor(obs)
        out, states = self.rnn(jnp.concatenate([feats, prev_actions.astype(feats.dtype)], -1), prev_states, mask)
        values = self.critic(out).astype(jnp.float32)
        x = self.actor_backbone(out)
        actor_outs = [head(x).astype(jnp.float32) for head in self.actor_heads]
        return actor_outs, values, states


class RecurrentPPOPlayer:
    """Single-step rollout policy with carried LSTM state (reference :265)."""

    def __init__(self, agent: RecurrentPPOAgent, params: Any, actions_dim: Sequence[int], num_envs: int):
        self.agent = agent
        self.params = params
        self.actions_dim = tuple(actions_dim)
        self.num_envs = num_envs

        def _env_actions(actions):
            if agent.is_continuous:
                return jnp.concatenate(actions, -1)
            return jnp.concatenate([a.argmax(-1, keepdims=True).astype(jnp.int32) for a in actions], -1)

        def _act(params, obs, prev_actions, prev_states, key, greedy):
            key, sub = jax.random.split(key)
            actor_outs, values, states = agent.apply(params, obs, prev_actions, prev_states)
            # single timestep: T == 1
            actions = sample_actions(
                [a[0] for a in actor_outs], sub, agent.is_continuous, agent.distribution, greedy=greedy
            )
            logp, _ = evaluate_actions(
                [a[0] for a in actor_outs], actions, agent.is_continuous, agent.distribution
            )
            cat = jnp.concatenate(actions, -1)
            # host_float32: rollout products are stored f32 in the buffers
            # (the dtype contract of utils.host_float32); states stay native.
            return host_float32((cat[None], _env_actions(actions), logp[None], values)) + (states, key)

        def _values(params, obs, prev_actions, prev_states):
            _, values, states = agent.apply(params, obs, prev_actions, prev_states)
            return host_float32(values[0]), states

        def _act_raw(params, obs, prev_actions, prev_states, key, greedy):
            # raw host obs [n_envs, ...] -> normalized [T=1, n_envs, ...] in-graph
            # (one dispatch per env step; see PPOPlayer.act_raw for the pattern)
            prepped = {}
            for k, v in obs.items():
                v = jnp.asarray(v, jnp.float32)
                if k in agent.cnn_keys:
                    v = v.reshape(v.shape[0], -1, *v.shape[-2:]) / 255.0 - 0.5
                else:
                    v = v.reshape(v.shape[0], -1)
                prepped[k] = v[None]
            return _act(params, prepped, prev_actions[None], prev_states, key, greedy)

        self._act = jax_compile.guarded_jit(_act, name="ppo_recurrent.act", static_argnums=(5,))
        self._act_raw = jax_compile.guarded_jit(_act_raw, name="ppo_recurrent.act_raw", static_argnums=(5,))
        self._values = jax_compile.guarded_jit(_values, name="ppo_recurrent.values")
        self._act_impl = _act
        self._packed_act_fns: Dict[Any, Any] = {}

    def initial_states(self, hidden_size: Optional[int] = None, num_envs: Optional[int] = None):
        hidden_size = hidden_size or self.agent.rnn_hidden_size
        n = num_envs or self.num_envs
        return (
            jnp.zeros((n, hidden_size), dtype=jnp.float32),
            jnp.zeros((n, hidden_size), dtype=jnp.float32),
        )

    def state_rows(self, states) -> Dict[str, jax.Array]:
        """The state a step was taken from, as the rollout's rows of that step."""
        return {"prev_hx": states[0], "prev_cx": states[1]}

    def reset_states(self, states, not_done: jax.Array):
        return tuple(not_done * s for s in states)

    def __call__(self, obs, prev_actions, prev_states, key, greedy: bool = False):
        return self._act(self.params, obs, prev_actions, prev_states, key, greedy)

    def act_raw(self, obs, prev_actions, prev_states, key, greedy: bool = False):
        """Raw host obs (no T dim, [0,255] cnn stacks) + prev_actions [n_envs, A]:
        normalization, T=1 expansion, and the forward run as ONE jitted dispatch."""
        return self._act_raw(self.params, obs, prev_actions, prev_states, key, greedy)

    def act_packed(self, codec, packed, prev_actions, prev_states, key, greedy: bool = False):
        """Like act_raw but fed by ONE packed host->device transfer (see
        core/pipeline.PackedObsCodec): unpack + normalize + T=1 expansion run
        in-graph; prev actions/states stay device-resident between steps."""
        cache_key = (codec.signature, bool(greedy))
        fn = self._packed_act_fns.get(cache_key)
        if fn is None:

            def _packed(params, packed, prev_actions, prev_states, key):
                obs = {k: v[None] for k, v in codec.decode_obs(packed).items()}
                return self._act_impl(params, obs, prev_actions[None], prev_states, key, greedy)

            fn = jax_compile.guarded_jit(_packed, name="ppo_recurrent.act_packed")
            self._packed_act_fns[cache_key] = fn
        return fn(self.params, packed, prev_actions, prev_states, key)

    def get_values(self, obs, prev_actions, prev_states):
        return self._values(self.params, obs, prev_actions, prev_states)


@jax_compile.setup_phase("build_agent")
def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg,
    obs_space: gymnasium.spaces.Dict,
    agent_state: Optional[Dict[str, Any]] = None,
) -> Tuple[RecurrentPPOAgent, Any, RecurrentPPOPlayer]:
    if str(cfg.algo.get("policy", "lstm")).lower() == "lm":
        from sheeprl_tpu.algos.ppo_recurrent.token_agent import build_token_agent

        return build_token_agent(runtime, actions_dim, is_continuous, cfg, obs_space, agent_state)
    distribution = cfg.distribution.get("type", "auto").lower()
    if distribution == "auto":
        distribution = "normal" if is_continuous else "discrete"
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    in_channels = sum(prod(obs_space[k].shape[:-2]) for k in cnn_keys)
    mlp_input_dim = sum(obs_space[k].shape[0] for k in mlp_keys)
    agent = RecurrentPPOAgent(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        distribution=distribution,
        cnn_keys=tuple(cnn_keys),
        mlp_keys=tuple(mlp_keys),
        cnn_input_channels=in_channels,
        mlp_input_dim=mlp_input_dim,
        screen_size=cfg.env.screen_size,
        encoder_cfg=dict(cfg.algo.encoder),
        rnn_cfg=dict(cfg.algo.rnn),
        actor_cfg=dict(cfg.algo.actor),
        critic_cfg=dict(cfg.algo.critic),
        dtype=runtime.compute_dtype,
    )
    n_envs = cfg.env.num_envs * runtime.world_size
    sample_obs = {}
    for k in cnn_keys:
        shape = obs_space[k].shape
        sample_obs[k] = jnp.zeros((1, 1, prod(shape[:-2]), *shape[-2:]), dtype=jnp.float32)
    for k in mlp_keys:
        sample_obs[k] = jnp.zeros((1, 1, *obs_space[k].shape), dtype=jnp.float32)
    h = cfg.algo.rnn.lstm.hidden_size
    init_states = (jnp.zeros((1, h)), jnp.zeros((1, h)))
    prev_actions = jnp.zeros((1, 1, sum(actions_dim)), dtype=jnp.float32)
    with jax_compile.setup_phase("build_agent.init"):
        params = agent.init(jax.random.PRNGKey(cfg.seed), sample_obs, prev_actions, init_states)
        if agent_state is not None:
            params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    with jax_compile.setup_phase("build_agent.place"):
        params = runtime.place_params(params)
        # player copy lives on the player device (host CPU by default): no accelerator
        # round-trip per env step (see sheeprl_tpu.core.runtime.Runtime.player_device)
        player_params = runtime.to_player(params)
    player = RecurrentPPOPlayer(agent, player_params, actions_dim, n_envs)
    return agent, params, player
