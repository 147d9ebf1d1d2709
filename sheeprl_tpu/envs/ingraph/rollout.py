"""Fused in-graph rollout: ``lax.scan`` over time of ``policy.act ∘ env.step``.

One jitted call per PPO/A2C iteration replaces ``rollout_steps`` host loop
bodies: the scan body samples actions with the player's unjitted ``_act_impl``
(the same fused normalize+sample+logprob trace the packed host path uses),
steps all ``B`` vmapped envs with auto-reset, and emits the rollout directly in
the ``DeviceRolloutBuffer`` layout — a dict of ``[T, B, ...]`` float32 leaves
with ``rewards``/``dones`` as ``[T, B, 1]`` — so the existing
``runtime.replicate((data, next_values))`` train handoff consumes it unchanged.
The bootstrap values for GAE come from one in-graph critic call on the final
obs, so a steady-state iteration performs ZERO per-step host transfers (pinned
by the ``jax.transfer_guard`` test in tests/test_envs/test_ingraph.py).

Truncation bootstrapping (the host loop's ``final_obs`` branch) happens
in-graph too: the critic is evaluated on ``info["terminal_obs"]`` and
``gamma * V(terminal_obs)`` is added to the stored reward where the step
truncated — one batched ``[T*B]`` critic call after the scan (thin per-step
critic calls cost about as much as the whole act chain on CPU) instead of a
padded host round-trip.

Episode accounting never touches the host on the hot path either: running
return/length accumulators ride in the carry and the per-step finished-episode
values come back as ``[T, B]`` metrics leaves, pulled (and iterated with
:func:`iter_finished_episodes`) only when metric logging asks for them.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.envs.ingraph.base import autoreset_step
from sheeprl_tpu.envs.ingraph.vector import Carry, InGraphVectorEnv

__all__ = ["InGraphRolloutCollector", "iter_finished_episodes"]


def iter_finished_episodes(metrics: Dict[str, Any]) -> Iterator[Tuple[float, int]]:
    """Yield ``(episode_return, episode_length)`` for every episode that ended
    inside a collected rollout (host-side; pulls the [T, B] metric leaves)."""
    done = np.asarray(metrics["dones"]) > 0
    rets = np.asarray(metrics["episode_returns"])
    lens = np.asarray(metrics["episode_lengths"])
    for t, b in zip(*np.nonzero(done)):
        yield float(rets[t, b]), int(lens[t, b])


class InGraphRolloutCollector:
    """Owns the jitted ``collect`` and the carry handoff with the driver.

    ``collect()`` reads ``venv.carry``, runs the fused scan, writes the new
    carry back (so a driver ``reset(seed=...)`` — health-sentinel reseed,
    chaos drill — transparently restarts the streams for the next call), and
    returns ``(data, metrics, next_values)`` with everything still on device.
    """

    def __init__(
        self,
        venv: InGraphVectorEnv,
        player: Any,
        rollout_steps: int,
        gamma: float,
        clip_rewards: bool = False,
        store_logprobs: bool = True,
        name: str = "ppo",
    ):
        self.venv = venv
        self.player = player
        self.rollout_steps = int(rollout_steps)
        env, params = venv.env, venv.env_params
        obs_key = venv.obs_key
        base_step = autoreset_step(env, params)
        act_impl = player._act_impl  # unjitted: fused into this trace
        values_impl = player._values_impl
        is_continuous = player.agent.is_continuous
        gamma = float(gamma)

        def to_env_action(env_actions):
            # player._env_actions emits [B, len(actions_dim)]: continuous envs
            # take the action vector, single-head discrete envs a scalar int
            if is_continuous:
                return env_actions
            return env_actions[:, 0]

        def one_step(policy_params, step, carry: Carry, _):
            obs = carry.obs
            cat_actions, env_actions, logp, values, key = act_impl(
                policy_params, {obs_key: obs}, carry.key
            )
            key, sub = jax.random.split(key)
            # batch size from the traced obs, NOT the closed-over venv.num_envs:
            # under shard_map the same trace runs on the [B/n_shards] local block
            step_keys = jax.random.split(sub, obs.shape[0])
            state, next_obs, reward, done, info = jax.vmap(step)(
                step_keys, carry.state, to_env_action(env_actions)
            )
            reward = reward.astype(jnp.float32)
            ep_ret = carry.ep_ret + reward
            ep_len = carry.ep_len + 1
            out = {
                obs_key: obs,
                "actions": cat_actions,
                "values": values,
                "rewards": reward[:, None],
                "dones": done.astype(jnp.float32)[:, None],
            }
            if store_logprobs:
                out["logprobs"] = logp
            step_metrics = {
                "episode_returns": jnp.where(done, ep_ret, 0.0),
                "episode_lengths": jnp.where(done, ep_len, 0),
                "dones": done.astype(jnp.float32),
            }
            new_carry = Carry(
                state=state,
                obs=next_obs,
                key=key,
                ep_ret=jnp.where(done, 0.0, ep_ret),
                ep_len=jnp.where(done, 0, ep_len),
            )
            aux = (info["terminal_obs"], info["truncated"].astype(jnp.float32))
            return new_carry, (out, step_metrics, aux)

        def collect(policy_params, carry: Carry, env_overrides=None):
            # the population trainer passes traced per-member EnvParams
            # overrides (domain randomization): the scan body must see the
            # override-closed step at trace time
            step = (
                base_step
                if env_overrides is None
                else autoreset_step(env, params.replace(**dict(env_overrides)))
            )
            # A FRESH body per trace, with the traced params bound to it: scan
            # caches the traced body by function identity, so one shared body
            # reading the params through a side slot replays the FIRST trace's
            # tracers on a retrace (UnexpectedTracerError under shard_map).
            carry, (data, metrics, aux) = jax.lax.scan(
                functools.partial(one_step, policy_params, step), carry, None, length=self.rollout_steps
            )
            # truncation bootstrap, in-graph (host path: ppo.py final_obs branch)
            # — computed as ONE batched [T*B] critic call after the scan instead
            # of T thin per-step calls, which costs about as much as the whole
            # act chain on CPU (the per-row math is identical)
            term_obs, truncated = aux
            v_term = values_impl(
                policy_params, {obs_key: term_obs.reshape((-1,) + term_obs.shape[2:])}
            )
            stored = data["rewards"][..., 0] + truncated * (
                gamma * v_term[:, 0].reshape(truncated.shape)
            )
            if clip_rewards:
                stored = jnp.tanh(stored)
            data = dict(data)
            data["rewards"] = stored[..., None]
            next_values = values_impl(policy_params, {obs_key: carry.obs})
            return carry, data, metrics, next_values

        # the unjitted impl is what the fused trainer (envs/ingraph/fused.py)
        # inlines into its whole-iteration trace — same expressions, so the
        # fused path stays bit-identical to collect_fn + train_fn run apart
        self.collect_impl = collect
        self.collect_fn = jax_compile.guarded_jit(collect, name=f"{name}.ingraph_collect")

    def collect(self):
        """One fused rollout. Returns ``(data, metrics, next_values)`` — the
        ``[T, B, ...]`` rollout dict, the ``[T, B]`` episode metrics, and the
        ``[B, 1]`` GAE bootstrap values — all on device, zero host transfers."""
        if self.venv.carry is None:
            raise RuntimeError("collect() before venv.reset()")
        carry, data, metrics, next_values = self.collect_fn(self.player.params, self.venv.carry)
        self.venv.carry = carry
        return data, metrics, next_values

    def warmup_specs(self):
        """(params_specs, carry_specs) for ``AOTWarmup.add(collect_fn, ...)``."""
        return (
            jax_compile.specs_of(self.player.params),
            jax_compile.specs_of(self.venv.carry),
        )

    def output_specs(self):
        """Abstract ``(data, next_values)`` shapes (``jax.eval_shape``: no FLOPs,
        no transfers) — the train step's warmup specs for zero-retrace runs."""
        _carry_s, data_s, _metrics_s, nv_s = jax.eval_shape(
            self.collect_fn.fun, *self.warmup_specs()
        )
        return data_s, nv_s
