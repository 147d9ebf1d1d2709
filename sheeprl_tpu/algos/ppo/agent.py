"""PPO agent (flax): shared MultiEncoder + actor heads + critic.

Parity with reference sheeprl/algos/ppo/agent.py (PPOAgent :91, PPOPlayer :242,
build_agent :325). JAX design: the module returns raw actor outputs + values; all
distribution math (sampling / log-prob / entropy) lives in pure functions so the same
module serves the jitted train step and the rollout player without DDP/single-device
twin modules.
"""

from __future__ import annotations

from math import prod
from typing import Any, Dict, List, Optional, Sequence, Tuple

import flax.linen as nn
import gymnasium
import jax
import jax.numpy as jnp

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.models.models import MLP, MultiEncoder, NatureCNN
from sheeprl_tpu.ops.distributions import Independent, Normal, OneHotCategorical
from sheeprl_tpu.utils.utils import host_float32, safeatanh, safetanh


class CNNEncoder(nn.Module):
    in_channels: int
    features_dim: int
    screen_size: int
    keys: Sequence[str]
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-3)
        return NatureCNN(
            in_channels=self.in_channels,
            features_dim=self.features_dim,
            screen_size=self.screen_size,
            dtype=self.dtype,
        )(x)


class MLPEncoder(nn.Module):
    input_dim: int
    features_dim: Optional[int]
    keys: Sequence[str]
    dense_units: int = 64
    mlp_layers: int = 2
    dense_act: str = "relu"
    layer_norm: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        if self.mlp_layers == 0:
            return x
        return MLP(
            input_dims=self.input_dim,
            output_dim=self.features_dim,
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.dense_act,
            layer_norm=self.layer_norm,
            dtype=self.dtype,
        )(x)


class PPOAgent(nn.Module):
    """Feature extractor + actor heads + critic. Returns (actor_outs, values)."""

    actions_dim: Sequence[int]
    is_continuous: bool
    distribution: str
    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_input_channels: int
    mlp_input_dim: int
    screen_size: int
    encoder_cfg: Dict[str, Any]
    actor_cfg: Dict[str, Any]
    critic_cfg: Dict[str, Any]
    dtype: Any = jnp.float32

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
        kernel_init = (
            nn.initializers.orthogonal(1.0) if self.encoder_cfg.get("ortho_init", False) else None
        )
        self.critic = MLP(
            input_dims=1,  # inferred at call; kept for API parity
            output_dim=1,
            hidden_sizes=[self.critic_cfg["dense_units"]] * self.critic_cfg["mlp_layers"],
            activation=self.critic_cfg["dense_act"],
            layer_norm=self.critic_cfg["layer_norm"],
            kernel_init=kernel_init,
        )
        self.actor_backbone = (
            MLP(
                input_dims=1,
                output_dim=None,
                hidden_sizes=[self.actor_cfg["dense_units"]] * self.actor_cfg["mlp_layers"],
                activation=self.actor_cfg["dense_act"],
                layer_norm=self.actor_cfg["layer_norm"],
                kernel_init=kernel_init,
            )
            if self.actor_cfg["mlp_layers"] > 0
            else None
        )
        if self.is_continuous:
            self.actor_heads = [nn.Dense(sum(self.actions_dim) * 2)]
        else:
            self.actor_heads = [nn.Dense(d) for d in self.actions_dim]

    def __call__(self, obs: Dict[str, jax.Array]) -> Tuple[List[jax.Array], jax.Array]:
        feat = self.feature_extractor(obs)
        values = self.critic(feat)
        x = self.actor_backbone(feat) if self.actor_backbone is not None else feat
        actor_outs = [head(x) for head in self.actor_heads]
        return actor_outs, values.astype(jnp.float32)


# ----------------------------------------------------------------------------------
# Pure distribution helpers shared by training and rollout
# ----------------------------------------------------------------------------------


def _continuous_dist(actor_out: jax.Array) -> Independent:
    mean, log_std = jnp.split(actor_out, 2, axis=-1)
    return Independent(Normal(mean, jnp.exp(log_std)), 1)


def sample_actions(
    actor_outs: List[jax.Array],
    key: jax.Array,
    is_continuous: bool,
    distribution: str,
    greedy: bool = False,
) -> List[jax.Array]:
    """Sample (or take the mode of) the policy distributions."""
    if is_continuous:
        dist = _continuous_dist(actor_outs[0])
        if greedy:
            actions = dist.base.loc
        else:
            actions = dist.rsample(key)
        if distribution == "tanh_normal":
            actions = safetanh(actions, eps=1e-6)
        return [actions]
    keys = jax.random.split(key, len(actor_outs))
    out = []
    for logits, k in zip(actor_outs, keys):
        d = OneHotCategorical(logits=logits.astype(jnp.float32))
        out.append(d.mode if greedy else d.sample(k))
    return out


def evaluate_actions(
    actor_outs: List[jax.Array],
    actions: List[jax.Array],
    is_continuous: bool,
    distribution: str,
) -> Tuple[jax.Array, jax.Array]:
    """Return (logprob[..., 1], entropy[..., 1]) for given actions (train path)."""
    if is_continuous:
        dist = _continuous_dist(actor_outs[0].astype(jnp.float32))
        act = actions[0]
        if distribution == "tanh_normal":
            pre = safeatanh(act, eps=1e-6)
            logp = dist.log_prob(pre) - 2.0 * (
                jnp.log(jnp.asarray(2.0)) - act - jax.nn.softplus(-2.0 * act)
            ).sum(-1)
            return logp[..., None], dist.entropy()[..., None]
        logp = dist.log_prob(act)
        return logp[..., None], dist.entropy()[..., None]
    logps, ents = [], []
    for logits, act in zip(actor_outs, actions):
        d = OneHotCategorical(logits=logits.astype(jnp.float32))
        logps.append(d.log_prob(act))
        ents.append(d.entropy())
    return (
        jnp.stack(logps, axis=-1).sum(axis=-1, keepdims=True),
        jnp.stack(ents, axis=-1).sum(axis=-1, keepdims=True),
    )


class PPOPlayer:
    """Rollout-side policy: holds params + jitted act/get_values (reference :242).

    Every per-step op — sampling, log-prob, the env-facing argmax/concat — is fused
    into ONE jitted call: eager ops cost a full dispatch round-trip on remote TPU
    backends, so the host loop only ever transfers results.
    """

    def __init__(self, agent: PPOAgent, params: Any, actions_dim: Sequence[int]):
        self.agent = agent
        self.params = params
        self.actions_dim = tuple(actions_dim)

        def _env_actions(actions: List[jax.Array]) -> jax.Array:
            if agent.is_continuous:
                return jnp.concatenate(actions, -1)
            return jnp.concatenate([a.argmax(-1, keepdims=True).astype(jnp.int32) for a in actions], -1)

        def _act(params, obs, key):
            key, sub = jax.random.split(key)
            actor_outs, values = agent.apply(params, obs)
            actions = sample_actions(actor_outs, sub, agent.is_continuous, agent.distribution)
            logp, _ = evaluate_actions(actor_outs, actions, agent.is_continuous, agent.distribution)
            # host_float32: rollout products are stored f32 in the buffers
            # (the dtype contract of utils.host_float32)
            return host_float32((jnp.concatenate(actions, -1), _env_actions(actions), logp, values)) + (key,)

        def _greedy(params, obs, key):
            key, sub = jax.random.split(key)
            actor_outs, _ = agent.apply(params, obs)
            actions = sample_actions(actor_outs, sub, agent.is_continuous, agent.distribution, greedy=True)
            return host_float32(_env_actions(actions)), key

        def _values(params, obs):
            _, values = agent.apply(params, obs)
            return host_float32(values)

        def _normalize(obs):
            # raw env obs -> the encoder's expected layout/ranges, in-graph: cnn
            # stacks arrive uint8-scaled [0,255] and become centered floats; mlp
            # obs flatten to [n_envs, features] (mirrors utils.prepare_obs)
            out = {}
            for k, v in obs.items():
                v = jnp.asarray(v, jnp.float32)
                if k in agent.cnn_keys:
                    # collapse any frame-stack dim into channels (idempotent for
                    # already-[n_envs, C, H, W] inputs)
                    out[k] = v.reshape(v.shape[0], -1, *v.shape[-2:]) / 255.0 - 0.5
                else:
                    out[k] = v.reshape(v.shape[0], -1)
            return out

        def _act_raw(params, obs, key):
            return _act(params, _normalize(obs), key)

        def _greedy_raw(params, obs, key):
            return _greedy(params, _normalize(obs), key)

        self._act = jax_compile.guarded_jit(_act, name="ppo.act")
        self._act_raw = jax_compile.guarded_jit(_act_raw, name="ppo.act_raw")
        self._greedy = jax_compile.guarded_jit(_greedy, name="ppo.greedy")
        self._greedy_raw = jax_compile.guarded_jit(_greedy_raw, name="ppo.greedy_raw")
        self._values = jax_compile.guarded_jit(_values, name="ppo.values")
        self._act_impl = _act  # unjitted: fused into the packed-act trace
        self._values_impl = _values  # unjitted: fused into the in-graph rollout scan
        self._greedy_impl = _greedy
        self._packed_act_fns: Dict[Any, Any] = {}

    def __call__(self, obs: Dict[str, jax.Array], key: jax.Array):
        """Returns (cat_actions, env_actions, logprobs, values, next_key) — all on device."""
        return self._act(self.params, obs, key)

    def act_raw(self, obs: Dict[str, Any], key: jax.Array):
        """Same as ``__call__`` but takes RAW host obs (mlp vectors + [0,255] cnn
        stacks, already shaped ``[n_envs, ...]``): the normalization runs inside
        the ONE jitted dispatch instead of as a separate eager prep + device_put
        per step (measured ~20% of the per-step rollout cost in the host loop).
        """
        return self._act_raw(self.params, obs, key)

    def act_packed(self, codec, packed: jax.Array, key: jax.Array):
        """Same as :meth:`act_raw` but over a ``PackedObsCodec`` transfer: the
        whole obs dict arrives as ONE packed ``device_put`` and is unpacked +
        normalized in-graph (``codec.decode_obs`` mirrors ``_normalize``
        bit-for-bit), so a steady-state step costs exactly one host->device
        transfer. One compile per codec layout (two codecs with equal-length
        buffers must not share a trace, hence the signature-keyed cache)."""
        return self.packed_act_fn(codec)(self.params, packed, key)

    def packed_act_fn(self, codec):
        """The guarded jitted packed-act entry point for ``codec`` (exposed so
        the train loop can register its AOT warmup before the rollout starts)."""
        fn = self._packed_act_fns.get(codec.signature)
        if fn is None:
            fn = jax_compile.guarded_jit(
                lambda params, packed, key: self._act_impl(params, codec.decode_obs(packed), key),
                name="ppo.act_packed",
            )
            self._packed_act_fns[codec.signature] = fn
        return fn

    def get_actions(self, obs: Dict[str, jax.Array], key: jax.Array, greedy: bool = False):
        """Returns (env-facing actions, next_key)."""
        if greedy:
            return self._greedy(self.params, obs, key)
        _, env_actions, _, _, key = self._act(self.params, obs, key)
        return env_actions, key

    def get_actions_raw(
        self, obs: Dict[str, Any], key: jax.Array, greedy: bool = False, params: Any = None
    ):
        """:meth:`get_actions` over RAW host obs (normalization fused in-graph,
        same single-dispatch rationale as :meth:`act_raw`). ``params`` overrides
        ``self.params`` so callers that swap weight generations atomically (the
        serve runtime) can pin a batch to one generation without mutating the
        shared player. Returns (env-facing actions, next_key)."""
        p = self.params if params is None else params
        if greedy:
            return self._greedy_raw(p, obs, key)
        _, env_actions, _, _, key = self._act_raw(p, obs, key)
        return env_actions, key

    def get_values(self, obs: Dict[str, jax.Array]) -> jax.Array:
        return self._values(self.params, obs)


def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg,
    obs_space: gymnasium.spaces.Dict,
    agent_state: Optional[Dict[str, Any]] = None,
) -> Tuple[PPOAgent, Any, PPOPlayer]:
    """Create the agent module, init (or restore) params, return (agent, params, player).

    Reference: build_agent sheeprl/algos/ppo/agent.py:325 (there it DDP-wraps the
    train module and clones a single-device player; here params are a single pytree
    replicated across the mesh — no wrapping needed).
    """
    distribution = cfg.distribution.get("type", "auto").lower()
    if distribution not in ("auto", "normal", "tanh_normal", "discrete"):
        raise ValueError(
            "The distribution must be on of: `auto`, `discrete`, `normal` and `tanh_normal`. "
            f"Found: {distribution}"
        )
    if distribution == "discrete" and is_continuous:
        raise ValueError("You have choose a discrete distribution but `is_continuous` is true")
    if distribution not in ("discrete", "auto") and not is_continuous:
        raise ValueError("You have choose a continuous distribution but `is_continuous` is false")
    if distribution == "auto":
        distribution = "normal" if is_continuous else "discrete"

    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    in_channels = sum(prod(obs_space[k].shape[:-2]) for k in cnn_keys)
    mlp_input_dim = sum(obs_space[k].shape[0] for k in mlp_keys)
    agent = PPOAgent(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        distribution=distribution,
        cnn_keys=tuple(cnn_keys),
        mlp_keys=tuple(mlp_keys),
        cnn_input_channels=in_channels,
        mlp_input_dim=mlp_input_dim,
        screen_size=cfg.env.screen_size,
        encoder_cfg=dict(cfg.algo.encoder),
        actor_cfg=dict(cfg.algo.actor),
        critic_cfg=dict(cfg.algo.critic),
        dtype=runtime.compute_dtype,
    )
    sample_obs = {}
    for k in cnn_keys:
        shape = obs_space[k].shape
        sample_obs[k] = jnp.zeros((1, prod(shape[:-2]), *shape[-2:]), dtype=jnp.float32)
    for k in mlp_keys:
        sample_obs[k] = jnp.zeros((1, *obs_space[k].shape), dtype=jnp.float32)
    params = agent.init(jax.random.PRNGKey(cfg.seed), sample_obs)
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    params = runtime.place_params(params)
    # The player's copy lives on the player device (host CPU by default): per-step
    # policy calls then never pay the accelerator round-trip (reference's
    # get_single_device_fabric split, sheeprl/utils/fabric.py:8-35).
    player = PPOPlayer(agent, runtime.to_player(params), actions_dim)
    return agent, params, player
