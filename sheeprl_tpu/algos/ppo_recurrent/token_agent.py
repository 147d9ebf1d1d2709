"""A language model (``models/lm.py``) as ``ppo_recurrent``'s sequence policy.

The actor's logits are the language model's over the held vocabulary, the critic
is one linear map on the final normed state. Observations are ``tokens`` (the id
shown at this step) and ``sampled`` (1 where the step's action is the next token
and counts, 0 while the env feeds a prompt); the action is a token id, stored as
one number a step, not as a one-hot row.

What differs from the LSTM through ``ppo_recurrent``'s seam: the carried state
(two gated inputs a conv layer, keys and values an attention layer, a position)
is a pytree that is never stored per step, so a training sequence has to start
where an episode starts (``starts_at_reset``) and is evaluated from the empty
state; prompt steps leave the loss through ``loss_mask``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import gymnasium
import jax
import jax.numpy as jnp

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.models import lm
from sheeprl_tpu.utils.utils import host_float32


def pick_tokens(logits: jax.Array, key: jax.Array, greedy: bool) -> jax.Array:
    """The next token of each sequence: the likeliest (``greedy``, a Python flag fixed when the act
    program is built) or a draw from the logits."""
    return jnp.argmax(logits, -1) if greedy else jax.random.categorical(key, logits, axis=-1)


class TokenPolicy:
    """The learner's side: teacher-forced evaluation of whole sequences."""

    starts_at_reset = True
    action_width = 1

    def __init__(self, config: lm.LMConfig, dtype: Any):
        self.config = config
        self.dtype = dtype
        self.actions_dim = (config.vocab_held,)

    def loss_mask(self, batch: Dict[str, jax.Array]) -> jax.Array:
        return batch["mask"] * batch["sampled"]

    def evaluate(self, params, batch: Dict[str, jax.Array], norm_obs: Dict[str, jax.Array]):
        """``batch`` [T, B, 1] rows -> (log-prob, entropy, value) [T, B, 1] and the expert layers' counters."""
        tokens = norm_obs["tokens"][..., 0].astype(jnp.int32).T  # ids are whole numbers, exact in float32
        actions = batch["actions"][..., 0].astype(jnp.int32).T
        logp, entropy, values, aux = lm.evaluate(params, tokens, actions, self.config, self.dtype)
        return logp.T[..., None], entropy.T[..., None], values.T[..., None], lm.moe_metrics(aux)


class TokenPlayer:
    """The actor's side: one token a step for every env through the carried state."""

    def __init__(self, agent: TokenPolicy, params: Any, num_envs: int, placement: Any = None):
        self.agent = agent
        self.params = params
        self.placement = placement  # where the carried state lives: a device or sharding, as the player's parameters
        self.actions_dim = agent.actions_dim
        self.num_envs = num_envs
        config, dtype = agent.config, agent.dtype

        def _act(params, obs, states, key, greedy):
            key, sub = jax.random.split(key)
            tokens = obs["tokens"].reshape(-1).astype(jnp.int32)
            logits, values, states = lm.decode_step(params, tokens, states, config, dtype)
            actions = pick_tokens(logits, sub, greedy)
            logp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1), actions[:, None], axis=-1)
            cat = actions[:, None].astype(jnp.float32)
            return host_float32((cat[None], actions[:, None].astype(jnp.int32), logp[None], values[None, :, None])) + (states, key)

        def _values(params, obs, states):
            tokens = obs["tokens"].reshape(-1).astype(jnp.int32)
            _, values, states = lm.decode_step(params, tokens, states, config, dtype)
            return host_float32(values[:, None]), states

        self._act_impl = _act
        self._act = jax_compile.guarded_jit(_act, name="ppo_recurrent.act", static_argnums=(4,))
        self._values = jax_compile.guarded_jit(_values, name="ppo_recurrent.values")
        self._packed_act_fns: Dict[Any, Any] = {}

    def initial_states(self, num_envs: Optional[int] = None):
        state = lm.init_state(self.agent.config, num_envs or self.num_envs, self.agent.dtype)
        # committed like every later state, so the first act compiles the one program all of them run
        return jax.device_put(state, self.placement) if self.placement is not None else state

    def state_rows(self, states) -> Dict[str, jax.Array]:
        return {}  # nothing of the state is stored per step: sequences start at resets

    def reset_states(self, states, not_done: jax.Array):
        return lm.reset_state(states, not_done.reshape(-1))

    def __call__(self, obs, prev_actions, prev_states, key, greedy: bool = False):
        del prev_actions  # the previous action comes back as this step's token
        return self._act(self.params, obs, prev_states, key, greedy)

    def act_packed(self, codec, packed, prev_actions, prev_states, key, greedy: bool = False):
        """Fed by ONE packed host->device transfer (``core/pipeline.PackedObsCodec``), as the LSTM player."""
        del prev_actions
        cache_key = (codec.signature, bool(greedy))
        fn = self._packed_act_fns.get(cache_key)
        if fn is None:

            def _packed(params, packed, prev_states, key):
                return self._act_impl(params, codec.decode_obs(packed), prev_states, key, greedy)

            fn = jax_compile.guarded_jit(_packed, name="ppo_recurrent.act_packed", donate_argnums=(2,))
            self._packed_act_fns[cache_key] = fn
        return fn(self.params, packed, prev_states, key)

    def get_values(self, obs, prev_actions, prev_states):
        del prev_actions
        return self._values(self.params, obs, prev_states)


def build_token_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg,
    obs_space: gymnasium.spaces.Dict,
    agent_state: Optional[Dict[str, Any]] = None,
) -> Tuple[TokenPolicy, Any, TokenPlayer]:
    config = lm.LMConfig.from_cfg(cfg.algo.lm)
    if is_continuous or tuple(actions_dim) != (config.vocab_held,):
        raise ValueError(
            f"a language-model policy acts over its held vocabulary ({config.vocab_held} ids); "
            f"the env's action space has dimensions {tuple(actions_dim)}"
        )
    for key in ("tokens", "sampled"):
        if key not in obs_space.spaces or key not in cfg.algo.mlp_keys.encoder:
            raise ValueError(f"a language-model policy needs the observation '{key}' among algo.mlp_keys.encoder")
    agent = TokenPolicy(config, runtime.compute_dtype)
    with jax_compile.setup_phase("build_agent.init"):
        if agent_state is not None:
            params = jax.tree_util.tree_map(jnp.asarray, agent_state)
        else:
            params = jax.jit(lambda key: lm.init_params(config, key))(jax.random.PRNGKey(cfg.seed))
    with jax_compile.setup_phase("build_agent.place"):
        params = runtime.place_params(params)
        player_params = runtime.to_player(params)
    n_envs = cfg.env.num_envs * runtime.world_size
    # the carried state lives beside the player's parameters, under their sharding (on the mesh device the
    # learner's replicated arrays: `main()` binds them), so that the act program is traced once
    placement = runtime.player_device if runtime.player_on_host else runtime.replicated
    player = TokenPlayer(agent, player_params, n_envs, placement)
    return agent, params, player
