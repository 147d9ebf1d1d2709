"""Recurrent PPO utilities (reference sheeprl/algos/ppo_recurrent/utils.py)."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs  # noqa: F401

AGGREGATOR_KEYS = {
    "Rewards/rew_avg",
    "Game/ep_len_avg",
    "Loss/value_loss",
    "Loss/policy_loss",
    "Loss/entropy_loss",
    "Resilience/env_restarts",
    "Resilience/env_timeouts",
    "Resilience/nonfinite_skips",
}
# Compilation-management counters (core/compile.py), drained once per iteration.
AGGREGATOR_KEYS |= {
    "Compile/retraces",
    "Compile/cache_hits",
    "Compile/cache_misses",
    "Time/compile_seconds",
}
MODELS_TO_REGISTER = {"agent"}


def test(player, runtime, cfg, log_dir: str) -> None:
    """Greedy evaluation episode with carried recurrent state (reference utils.py:37)."""
    from sheeprl_tpu.utils.env import make_env

    env = make_env(cfg, cfg.seed, 0, log_dir, "test", vector_env_idx=0)()
    done = False
    cumulative_rew = 0.0
    obs = env.reset(seed=cfg.seed)[0]
    # committed where the player is, like the key every later step returns: the act program is traced once
    key = jax.device_put(jax.random.PRNGKey(cfg.seed), runtime.player_device)
    states = player.initial_states(num_envs=1)
    prev_actions = jnp.zeros((1, 1, player.agent.action_width), dtype=jnp.float32)
    while not done:
        jax_obs = prepare_obs(runtime, obs, cnn_keys=cfg.algo.cnn_keys.encoder)
        jax_obs = {k: v[None] for k, v in jax_obs.items()}
        cat_actions, env_actions, _, _, states, key = player(jax_obs, prev_actions, states, key, greedy=True)
        prev_actions = cat_actions
        real_actions = np.asarray(env_actions)[0]
        obs, reward, terminated, truncated, _ = env.step(
            np.asarray(real_actions).reshape(env.action_space.shape)
        )
        done = terminated or truncated
        cumulative_rew += reward
        if cfg.dry_run:
            done = True
    if cfg.metric.log_level > 0:
        runtime.print(f"Test - Reward: {cumulative_rew}")
        if getattr(runtime, "logger", None) is not None:
            runtime.logger.log_metrics({"Test/cumulative_reward": cumulative_rew}, 0)
    env.close()

# Single-'agent' registration shared with the other model-free algos.
from sheeprl_tpu.utils.model_manager import log_agent_from_checkpoint as log_models_from_checkpoint  # noqa: E402, F401
