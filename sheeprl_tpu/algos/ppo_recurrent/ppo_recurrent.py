"""Recurrent PPO (reference sheeprl/algos/ppo_recurrent/ppo_recurrent.py:31-120 train,
:120 main).

BPTT over sequence chunks. Host side splits the rollout into per-env episodes, chunks
them to ``per_rank_sequence_length``, pads, and buckets the sequence count to a
power-of-two so the jitted train function (epochs x minibatches via ``lax.scan``,
masked losses) retraces only on bucket growth — not every iteration.
"""

from __future__ import annotations

import contextlib
import os
import warnings
from typing import Any, Dict, List

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs
from sheeprl_tpu.algos.ppo_recurrent.agent import build_agent, evaluate_actions
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.core import health as health_mod
from sheeprl_tpu.core import resilience
from sheeprl_tpu.core.pipeline import AsyncEnvStepper, PackedObsCodec, pipeline_enabled
from sheeprl_tpu.data.factory import make_rollout_buffer
from sheeprl_tpu.telemetry import trace
from sheeprl_tpu.utils.env import finished_episodes, make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.optim import with_clipping
from sheeprl_tpu.utils.profiler import TraceProfiler
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import PlayerParamsSync, gae, polynomial_decay, save_configs


def _masked_mean(x: jax.Array, mask: jax.Array) -> jax.Array:
    return (x * mask).sum() / jnp.clip(mask.sum(), 1, None)


@jax_compile.setup_phase("make_train_fn")
def make_train_fn(agent, tx, cfg, runtime, obs_keys, cnn_keys, params_sync=None):
    update_epochs = int(cfg.algo.update_epochs)
    n_batches = max(int(cfg.algo.per_rank_num_batches), 1)
    data_sharding = NamedSharding(runtime.mesh, P(None, "data"))
    nonfinite_guard = resilience.guard_enabled(resilience.resolve(cfg))

    def loss_fn(params, batch, clip_coef, ent_coef):
        norm_obs = normalize_obs(batch, cnn_keys, obs_keys)
        mask = agent.loss_mask(batch)
        new_logprobs, entropy, values, extras = agent.evaluate(params, batch, norm_obs)
        advantages = batch["advantages"]
        if cfg.algo.normalize_advantages:
            # masked normalization (reference ppo_recurrent.py:77-81)
            n = jnp.clip(mask.sum(), 1, None)
            mean = (advantages * mask).sum() / n
            var = (((advantages - mean) * mask) ** 2).sum() / n
            advantages = (advantages - mean) / (jnp.sqrt(var) + 1e-8) * mask
        pg = policy_loss(new_logprobs, batch["logprobs"], advantages, clip_coef, "none")
        pg_loss = _masked_mean(pg, mask)
        if cfg.algo.clip_vloss:
            v_unclipped = (values - batch["returns"]) ** 2
            v_clipped_pred = batch["values"] + jnp.clip(values - batch["values"], -clip_coef, clip_coef)
            v_clipped = (v_clipped_pred - batch["returns"]) ** 2
            v_loss = 0.5 * _masked_mean(jnp.maximum(v_unclipped, v_clipped), mask)
        else:
            v_loss = _masked_mean((values - batch["returns"]) ** 2, mask)
        ent_loss = -_masked_mean(entropy, mask)
        total = pg_loss + cfg.algo.vf_coef * v_loss + cfg.algo.ent_coef * ent_loss
        return total, ((pg_loss, v_loss, ent_loss), extras)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train(params, opt_state, data, key, clip_coef, ent_coef, lr_scale):
        n_seq = next(iter(data.values())).shape[1]
        batch_size = max(n_seq // n_batches, 1)
        n_mb = n_seq // batch_size

        epoch_keys = jax.random.split(key, update_epochs)
        perms = jnp.stack([jax.random.permutation(k, n_seq)[: n_mb * batch_size] for k in epoch_keys])
        perms = perms.reshape(update_epochs * n_mb, batch_size)

        def minibatch_step(carry, idx):
            params, opt_state = carry
            batch = jax.tree_util.tree_map(
                lambda v: jax.lax.with_sharding_constraint(jnp.take(v, idx, axis=1), data_sharding), data
            )
            with jax.named_scope("ppo.loss"):
                (loss, ((pg, vl, ent), extras)), grads = grad_fn(params, batch, clip_coef, ent_coef)
            with jax.named_scope("ppo.opt"):
                gnorm = optax.global_norm(grads)
                updates, new_opt_state = tx.update(grads, opt_state, params)
                # health-sentinel LR backoff: traced scalar operand; 1.0 is IEEE-exact
                updates = jax.tree_util.tree_map(lambda u: u * lr_scale, updates)
                new_params = optax.apply_updates(params, updates)
                if nonfinite_guard:
                    (params, opt_state), skipped = resilience.finite_or_skip(
                        (loss, gnorm), (new_params, new_opt_state), (params, opt_state)
                    )
                else:
                    params, opt_state, skipped = new_params, new_opt_state, jnp.float32(0.0)
            return (params, opt_state), (jnp.stack([pg, vl, ent, skipped, gnorm]), extras)

        (params, opt_state), (losses, extras) = jax.lax.scan(minibatch_step, (params, opt_state), perms)
        metrics = losses.mean(axis=0)
        flat_params = params_sync.ravel(params) if params_sync is not None else jnp.zeros(())
        return params, opt_state, flat_params, {
            "Loss/policy_loss": metrics[0],
            "Loss/value_loss": metrics[1],
            "Loss/entropy_loss": metrics[2],
            "Resilience/nonfinite_skips": losses[:, 3].sum(),
            "Grads/global_norm": metrics[4],
            # the policy's own counters (a language model's expert layers), of the last minibatch
            **{k: v[-1] for k, v in extras.items()},
        }

    return jax_compile.guarded_jit(train, name="ppo_recurrent.train", donate_argnums=(0, 1))


def _chunk_and_pad(
    local_data: Dict[str, np.ndarray], dones: np.ndarray, sl: int, n_envs: int, starts_at_reset: bool = False
):
    """Split the rollout into per-env episodes, chunk to length <= sl, pad + mask.

    Returns dict of arrays [sl, n_seq_padded, ...] with a `mask` key; n_seq is
    bucketed to the next power of two (zero-mask padding) for jit-shape stability.
    ``starts_at_reset``: the policy evaluates every sequence from its empty state,
    so each sequence has to be a whole episode; anything else is an error here.
    """
    sequences: Dict[str, List[np.ndarray]] = {k: [] for k in local_data.keys()}
    lengths: List[int] = []
    T = next(iter(local_data.values())).shape[0]
    for env_id in range(n_envs):
        ends = np.nonzero(dones[:, env_id, 0])[0].tolist()
        if starts_at_reset:
            episode_lengths = np.diff([-1] + ends)
            if not ends or ends[-1] != T - 1 or episode_lengths.max() > sl:
                raise ValueError(
                    "This policy keeps no state per step, so every training sequence must start at a reset and hold "
                    f"a whole episode: env {env_id} ended episodes at steps {ends[:8]} of a rollout of {T} steps with "
                    f"algo.per_rank_sequence_length={sl}. Make algo.rollout_steps a multiple of the episode length "
                    "and algo.per_rank_sequence_length at least the episode length."
                )
        ends.append(T - 1)
        start = 0
        for stop in ends:
            if stop + 1 <= start:
                continue
            ep_slice = slice(start, stop + 1)
            ep_len = stop + 1 - start
            for s0 in range(0, ep_len, sl):
                s1 = min(s0 + sl, ep_len)
                for k, v in local_data.items():
                    sequences[k].append(v[ep_slice][s0:s1, env_id])
                lengths.append(s1 - s0)
            start = stop + 1
    return jax_compile.bucketed_pad(sequences, lengths, sl)


# `utils.gae` called eagerly traces and lowers its reverse scan anew in every call: 45-53 ms for a rollout
# of 8,192 steps, on whichever device (my runs, PR 29). Compiled once it takes 0.4 ms, and gives the same bits.
_gae = jax_compile.guarded_jit(gae, name="ppo_recurrent.gae", static_argnums=(4, 5, 6))


def rollout_feed(
    local_data: Dict[str, np.ndarray],
    next_values: np.ndarray,
    cfg,
    n_envs: int,
    starts_at_reset: bool = False,
    host_device: Any = None,
) -> Dict[str, jax.Array]:
    """A finished rollout ``[T, n_envs, ...]`` on the host -> the train call's data on
    the device: GAE, the split into sequences (padded, masked, bucketed) and one
    transfer per key. ``main()`` and the chip benchmark's window both call this.

    GAE runs where the rollout is, on ``host_device``: its reverse scan is one tiny
    step for each of the rollout's steps, nothing for an accelerator, and the rollout
    need not travel there and back for it."""
    with trace.span("rollout.feed") as sp:
        with jax.default_device(host_device) if host_device is not None else contextlib.nullcontext():
            returns, advantages = _gae(
                jnp.asarray(local_data["rewards"]),
                jnp.asarray(local_data["values"]),
                jnp.asarray(local_data["dones"]),
                next_values,
                cfg.algo.rollout_steps,
                cfg.algo.gamma,
                cfg.algo.gae_lambda,
            )
        local_data["returns"] = np.asarray(returns, dtype=np.float32)
        local_data["advantages"] = np.asarray(advantages, dtype=np.float32)
        padded = _chunk_and_pad(
            local_data, local_data["dones"], cfg.algo.per_rank_sequence_length, n_envs, starts_at_reset
        )
        sp.set(bytes=sum(v.nbytes for v in padded.values()))
        return {k: jnp.asarray(v) for k, v in padded.items()}


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    if "minedojo" in cfg.env.wrapper._target_.lower():
        raise ValueError("MineDojo is not currently supported by PPO-recurrent agent.")
    initial_ent_coef = float(cfg.algo.ent_coef)
    initial_clip_coef = float(cfg.algo.clip_coef)
    world_size = runtime.world_size

    state = None
    if cfg.checkpoint.resume_from:
        from sheeprl_tpu.utils.checkpoint import load_state

        state = load_state(cfg.checkpoint.resume_from)

    logger = get_logger(runtime, cfg)
    if logger:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.logger = logger
    runtime.print(f"Log dir: {log_dir}")

    ft = resilience.resolve(cfg)
    sentinel = health_mod.HealthSentinel(
        cfg, log_dir=log_dir if runtime.is_global_zero else None, world_size=world_size
    )
    n_envs = cfg.env.num_envs * world_size
    envs = resilience.make_supervised_env(
        [
            make_env(cfg, cfg.seed + i, 0, log_dir if runtime.is_global_zero else None, "train", vector_env_idx=i)
            for i in range(n_envs)
        ],
        sync=cfg.env.sync_env,
        ft=ft,
    )
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder
    cnn_keys = cfg.algo.cnn_keys.encoder
    if cfg.algo.rollout_steps % cfg.algo.per_rank_sequence_length != 0:
        raise ValueError(
            "The rollout steps must be a multiple of the per_rank_sequence_length, got "
            f"{cfg.algo.rollout_steps} and {cfg.algo.per_rank_sequence_length}"
        )
    if str(cfg.algo.get("policy", "lstm")).lower() == "lm":
        # a policy whose sequences must start at resets: it stores no state per step, so a training
        # sequence is a whole episode, evaluated from the empty state, and fits the model's positions
        if not cfg.algo.reset_recurrent_state_on_done:
            raise ValueError(
                "algo.policy=lm evaluates every training sequence from the empty state, so its state has to be "
                "reset when an episode ends: set algo.reset_recurrent_state_on_done=True"
            )
        if cfg.algo.per_rank_sequence_length > cfg.algo.lm.max_positions:
            raise ValueError(
                "algo.policy=lm: a training sequence is a whole episode and has to fit the model's positions, got "
                f"algo.per_rank_sequence_length={cfg.algo.per_rank_sequence_length} and "
                f"algo.lm.max_positions={cfg.algo.lm.max_positions}"
            )
        if cfg.buffer.get("backend", "host") != "host":
            raise ValueError("algo.policy=lm keeps its rollout on the host (buffer.backend=host)")

    is_continuous = isinstance(envs.single_action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(envs.single_action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        envs.single_action_space.shape
        if is_continuous
        else (envs.single_action_space.nvec.tolist() if is_multidiscrete else [envs.single_action_space.n])
    )

    agent, params, player = build_agent(
        runtime, actions_dim, is_continuous, cfg, observation_space, state["agent"] if state else None
    )

    policy_steps_per_iter = int(n_envs * cfg.algo.rollout_steps)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    tx = with_clipping(instantiate(dict(cfg.algo.optimizer))(), cfg.algo.max_grad_norm)
    opt_state = tx.init(params)
    if state:
        opt_state = jax.tree_util.tree_map(jnp.asarray, state["optimizer"])
    opt_state = runtime.place_params(opt_state)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(cfg.metric.aggregator)

    rb = make_rollout_buffer(cfg, runtime, n_envs, obs_keys, log_dir)
    # device backend: policy outputs AND recurrent states stay in HBM per step;
    # the episode chunking below still runs on host, fed by ONE bulk pull per
    # iteration (rollout_host) instead of per-step np.asarray syncs
    device_rollout = getattr(rb, "backend", "host") == "device"

    last_train = 0
    train_step = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state else 1
    policy_step = state["iter_num"] * cfg.env.num_envs * cfg.algo.rollout_steps if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0

    # a player on the mesh device acts with the learner's own arrays (rebound after every train
    # call, as DreamerPlayerSync does): no flat copy of the parameters is made or moved
    params_sync = PlayerParamsSync(player.params) if runtime.player_on_host else None
    if params_sync is None:
        player.params = params  # from the first step on, not only after the first update: one sharding, one trace
    train_fn = make_train_fn(agent, tx, cfg, runtime, obs_keys, cnn_keys, params_sync)
    profiler = TraceProfiler(cfg.metric.get("profiler"), log_dir if runtime.is_global_zero else None)
    rng = jax.random.PRNGKey(cfg.seed)
    # where the player's key lives: beside its parameters, under their sharding, so that the act program is
    # traced once (a player on the mesh device has the learner's replicated arrays, not single-device ones)
    player_placement = runtime.player_device if runtime.player_on_host else runtime.replicated
    player_rng = jax.device_put(jax.random.PRNGKey(cfg.seed + 1), player_placement)
    if state and "rng" in state:
        rng = jnp.asarray(state["rng"])
        player_rng = jax.device_put(jnp.asarray(state["player_rng"]), player_placement)

    step_data = {}
    reset_obs = envs.reset(seed=cfg.seed)[0]
    next_obs = {}
    for k in obs_keys:
        _obs = reset_obs[k]
        if k in cnn_keys:
            _obs = _obs.reshape(n_envs, -1, *_obs.shape[-2:])
        next_obs[k] = _obs
        step_data[k] = _obs[np.newaxis]
    prev_states = player.initial_states()
    prev_actions = np.zeros((n_envs, agent.action_width), dtype=np.float32)

    # ----- software pipeline (core/pipeline.py): same structure as ppo.py; the
    # recurrent state feedback (prev_actions/prev_states) stays immediate after
    # step_wait because the NEXT act depends on it, everything else is deferred
    stepper = AsyncEnvStepper(envs, enabled=pipeline_enabled(cfg))
    codec = PackedObsCodec(cnn_keys=cnn_keys, device=runtime.player_device)
    zero_extra = {
        "rewards": np.zeros((n_envs, 1), np.float32),
        "dones": np.zeros((n_envs, 1), np.float32),
    }
    pending: Dict[str, Any] = {}

    def _process_pending(cur_packed):
        """Close out the previous step while the env workers run (see ppo.py)."""
        if not pending:
            return
        if device_rollout:
            if cur_packed is not None:
                extra_packed, extra_only = cur_packed, False
            else:
                extra_packed, extra_only = (
                    codec.encode_extra_only(
                        {"rewards": pending["rewards"], "dones": pending["dones"]}
                    ),
                    True,
                )
            rb.add_env_packed(codec, pending["packed"], extra_packed, extra_only=extra_only)
        else:
            step_data["dones"] = pending["dones"][np.newaxis]
            step_data["values"] = np.asarray(pending["values"])[np.newaxis].reshape(1, n_envs, 1)
            step_data["actions"] = np.asarray(pending["cat_actions"]).reshape(1, n_envs, -1)
            step_data["logprobs"] = np.asarray(pending["logprobs"]).reshape(1, n_envs, 1)
            step_data["rewards"] = pending["rewards"][np.newaxis]
            for k, v in pending["state_rows"].items():
                step_data[k] = np.asarray(v).reshape(1, n_envs, -1)
            step_data["prev_actions"] = np.asarray(pending["prev_actions"]).reshape(1, n_envs, -1)
            rb.add(step_data, validate_args=cfg.buffer.validate_args)
            for k in obs_keys:
                step_data[k] = next_obs[k][np.newaxis]
        if cfg.metric.log_level > 0:
            for i, (ep_rew, ep_len) in enumerate(finished_episodes(pending["info"])):
                if aggregator and "Rewards/rew_avg" in aggregator:
                    aggregator.update("Rewards/rew_avg", ep_rew)
                if aggregator and "Game/ep_len_avg" in aggregator:
                    aggregator.update("Game/ep_len_avg", ep_len)
                runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep_rew}")
        pending.clear()

    def _ckpt_state():
        # shared by the periodic checkpoint and the preemption emergency save so
        # both are resumable through the identical path; the rng chains make the
        # resumed run BIT-IDENTICAL to an uninterrupted one
        return {
            "agent": jax.device_get(params),
            "optimizer": jax.device_get(opt_state),
            "iter_num": iter_num * world_size,
            "batch_size": -1,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "rng": jax.device_get(rng),
            "player_rng": jax.device_get(player_rng),
        }

    guard = resilience.PreemptionGuard(
        enabled=ft.preemption.enabled, stop_after_iters=ft.preemption.stop_after_iters
    )
    with guard:
        for iter_num in range(start_iter, total_iters + 1):
            profiler.step(policy_step)
            for _ in range(cfg.algo.rollout_steps):
                policy_step += n_envs

                with timer("Time/env_interaction_time", SumMetric()):
                    # ONE packed host->device transfer per step: obs plus the
                    # previous step's rewards/dones; prev actions/states already
                    # live on the device (see RecurrentPPOPlayer.act_packed)
                    packed = codec.encode(
                        next_obs,
                        extra={"rewards": pending["rewards"], "dones": pending["dones"]}
                        if pending
                        else zero_extra,
                    )
                    state_rows = player.state_rows(prev_states)  # before the act: it may donate the state
                    with trace.span("player.decode"):
                        cat_actions, env_actions, logprobs, values, states, player_rng = player.act_packed(
                            codec,
                            packed,
                            prev_actions,
                            prev_states,
                            player_rng,
                        )
                        real_actions = np.asarray(env_actions)
                    stepper.step_async(real_actions.reshape(envs.action_space.shape))

                    # ---- overlap window: env workers are stepping; close out the
                    # previous step and scatter this one's policy row in-graph
                    _process_pending(packed)
                    if device_rollout:
                        # policy outputs + the recurrent state that PRODUCED this
                        # step: all scattered in-graph, no per-step host pull
                        rb.add_policy(
                            {
                                "values": jnp.reshape(values, (n_envs, 1)),
                                "actions": jnp.reshape(cat_actions, (n_envs, -1)),
                                "logprobs": jnp.reshape(logprobs, (n_envs, 1)),
                                **{k: jnp.reshape(v, (n_envs, -1)) for k, v in state_rows.items()},
                                "prev_actions": jnp.reshape(jnp.asarray(prev_actions), (n_envs, -1)),
                            }
                        )

                    obs, rewards, terminated, truncated, info = stepper.step_wait()
                    rewards = np.asarray(rewards, dtype=np.float32)
                    # bootstrap on truncation (reference ppo_recurrent.py:312-336)
                    truncated_envs = np.nonzero(truncated)[0]
                    if len(truncated_envs) > 0 and "final_obs" in info:
                        final_obs_arr = np.asarray(info["final_obs"], dtype=object)
                        for te in truncated_envs:
                            fo = final_obs_arr[te]
                            if fo is None:
                                continue
                            f_obs = {}
                            for k in obs_keys:
                                v = np.asarray(fo[k], dtype=np.float32)
                                if k in cnn_keys:
                                    v = v.reshape(-1, *v.shape[-2:]) / 255.0 - 0.5
                                f_obs[k] = jnp.asarray(v)[None, None]
                            te_states = jax.tree_util.tree_map(lambda s: s[te : te + 1], states)
                            te_prev_act = jnp.asarray(cat_actions).reshape(n_envs, -1)[te : te + 1][None]
                            val, _ = player.get_values(f_obs, te_prev_act, te_states)
                            rewards[te] += cfg.algo.gamma * float(np.asarray(val).reshape(-1)[0])
                    dones = np.logical_or(terminated, truncated).reshape(n_envs, -1).astype(np.float32)
                    rewards = rewards.reshape(n_envs, -1)

                # env products become the next step's pending work (the row write
                # and episode accounting run in the NEXT overlap window); the
                # act-time recurrent state is captured before the feedback below
                pending.update(
                    packed=packed,
                    rewards=rewards,
                    dones=dones,
                    info=info,
                    values=values,
                    cat_actions=cat_actions,
                    logprobs=logprobs,
                    state_rows=state_rows,
                    prev_actions=prev_actions,
                )

                if device_rollout:
                    # prev action feedback stays device-side (the dones put is
                    # small and async)
                    prev_actions = jnp.asarray(1.0 - dones, dtype=jnp.float32) * jnp.reshape(
                        cat_actions, (n_envs, -1)
                    )
                else:
                    prev_actions = (1 - dones) * np.asarray(cat_actions).reshape(n_envs, -1)

                # reset recurrent state on done (reference :356-371)
                if cfg.algo.reset_recurrent_state_on_done:
                    not_done = jnp.asarray(1.0 - dones, dtype=jnp.float32)
                    prev_states = player.reset_states(states, not_done)
                else:
                    prev_states = states

                next_obs = {}
                for k in obs_keys:
                    _obs = obs[k]
                    if k in cnn_keys:
                        _obs = _obs.reshape(n_envs, -1, *_obs.shape[-2:])
                    next_obs[k] = _obs

            with timer("Time/env_interaction_time", SumMetric()):
                # flush: the rollout's last row has no next act transfer to ride
                _process_pending(None)

            # device path: ONE bulk de-layout pull feeds the host-side episode
            # chunking (variable-length episode splitting is inherently host work)
            local_data = rb.rollout_host() if device_rollout else rb.to_arrays(dtype=np.float32)
            with timer("Time/train_time", SumMetric()):
                jax_obs = prepare_obs(runtime, next_obs, cnn_keys=cnn_keys, num_envs=n_envs)
                jax_obs = {k: v[None] for k, v in jax_obs.items()}
                next_values = np.asarray(
                    player.get_values(
                        jax_obs,
                        jax.device_put(np.asarray(prev_actions)[None], runtime.player_device),
                        prev_states,
                    )[0]
                )
                device_data = rollout_feed(
                    local_data, next_values, cfg, n_envs, agent.starts_at_reset, runtime.host_device
                )
                rng, train_key = jax.random.split(rng)
                params, opt_state, flat_params, train_metrics = train_fn(
                    params,
                    opt_state,
                    device_data,
                    train_key,
                    jnp.float32(cfg.algo.clip_coef),
                    jnp.float32(cfg.algo.ent_coef),
                    jnp.float32(sentinel.lr_scale),
                )
                player.params = (
                    params_sync.pull(flat_params, runtime.player_device) if params_sync is not None else params
                )
                if not timer.disabled:  # sync only when the train phase is being timed
                    jax.block_until_ready(params)
            train_step += world_size

            if cfg.metric.log_level > 0:
                if aggregator:
                    aggregator.update_from_device(train_metrics)
                if policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters:
                    overlap_s, overlap_steps = stepper.drain_overlap()
                    if overlap_s > 0:
                        sps_overlap = overlap_steps * n_envs * cfg.env.action_repeat / overlap_s
                        if aggregator and "Time/sps_pipeline_overlap" in aggregator:
                            aggregator.update("Time/sps_pipeline_overlap", sps_overlap)
                        else:
                            logger.log_metrics({"Time/sps_pipeline_overlap": sps_overlap}, policy_step)
                    if aggregator and not aggregator.disabled:
                        logger.log_metrics(aggregator.compute(), policy_step)
                        aggregator.reset()
                    if not timer.disabled:
                        timer_metrics = timer.compute()
                        if timer_metrics.get("Time/train_time", 0) > 0:
                            logger.log_metrics(
                                {"Time/sps_train": (train_step - last_train) / timer_metrics["Time/train_time"]},
                                policy_step,
                            )
                        if timer_metrics.get("Time/env_interaction_time", 0) > 0:
                            logger.log_metrics(
                                {
                                    "Time/sps_env_interaction": (
                                        (policy_step - last_log) / world_size * cfg.env.action_repeat
                                    )
                                    / timer_metrics["Time/env_interaction_time"]
                                },
                                policy_step,
                            )
                        timer.reset()
                    last_log = policy_step
                    last_train = train_step

            if cfg.algo.anneal_clip_coef:
                cfg.algo.clip_coef = polynomial_decay(
                    iter_num, initial=initial_clip_coef, final=0.0, max_decay_steps=total_iters, power=1.0
                )
            if cfg.algo.anneal_ent_coef:
                cfg.algo.ent_coef = polynomial_decay(
                    iter_num, initial=initial_ent_coef, final=0.0, max_decay_steps=total_iters, power=1.0
                )

            resilience.enforce_nonfinite_policy(ft, train_metrics)
            env_deltas = resilience.drain_env_counters(envs, aggregator)
            jax_compile.drain_compile_counters(aggregator)
            if iter_num == start_iter:
                # first iteration compiled every reachable signature for the
                # CURRENT bucket set; later buckets are legitimate first
                # compiles per signature, drift shows up as Compile/retraces
                jax_compile.mark_steady()

            # ----- health sentinel: warn -> backoff (lr_scale) -> rollback
            action = sentinel.observe(policy_step, train_metrics=train_metrics, env_counters=env_deltas)
            if action.rollback:
                rb_state = sentinel.take_rollback_state(os.path.join(log_dir, "checkpoint"))
                if rb_state is not None:
                    params = runtime.place_params(
                        jax.tree_util.tree_map(jnp.asarray, rb_state["agent"])
                    )
                    opt_state = runtime.place_params(
                        jax.tree_util.tree_map(jnp.asarray, rb_state["optimizer"])
                    )
                    if "rng" in rb_state:
                        rng = jnp.asarray(rb_state["rng"])
                        player_rng = jax.device_put(
                            jnp.asarray(rb_state["player_rng"]), player_placement
                        )
                    player.params = (
                        params_sync.pull(params_sync.ravel(params), runtime.player_device)
                        if params_sync is not None
                        else params
                    )
                    if sentinel.reseed_envs:
                        # fresh episode streams AND a clean recurrent state: the
                        # in-flight hidden state was produced by the poisoned policy
                        pending.clear()
                        reset_obs = envs.reset(seed=cfg.seed + iter_num)[0]
                        next_obs = {}
                        for k in obs_keys:
                            _obs = reset_obs[k]
                            if k in cnn_keys:
                                _obs = _obs.reshape(n_envs, -1, *_obs.shape[-2:])
                            next_obs[k] = _obs
                            step_data[k] = _obs[np.newaxis]
                        prev_states = player.initial_states()
                        prev_actions = np.zeros((n_envs, agent.action_width), dtype=np.float32)
                    runtime.print(
                        f"Health rollback at policy_step={policy_step}: restored certified "
                        "checkpoint, training continues."
                    )
            sentinel.drain(aggregator)

            if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
                iter_num == total_iters and cfg.checkpoint.save_last
            ):
                last_checkpoint = policy_step
                ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{runtime.global_rank}.ckpt")
                runtime.call(
                    "on_checkpoint_coupled",
                    ckpt_path=ckpt_path,
                    state=_ckpt_state(),
                    healthy=sentinel.certifiable,
                    policy_step=policy_step,
                )

            guard.completed_iteration()
            if guard.should_stop:
                if last_checkpoint != policy_step:  # periodic save above already covered this step
                    last_checkpoint = policy_step
                    ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{runtime.global_rank}.ckpt")
                    runtime.call(
                        "on_checkpoint_coupled",
                        ckpt_path=ckpt_path,
                        state=_ckpt_state(),
                        healthy=sentinel.certifiable,
                        policy_step=policy_step,
                    )
                runtime.print(
                    f"Preemption ({guard.describe()}) at iteration {iter_num}: emergency "
                    "checkpoint saved, exiting cleanly for resume."
                )
                break

    profiler.close()
    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test:
        from sheeprl_tpu.algos.ppo_recurrent.utils import test

        test(player, runtime, cfg, log_dir)
    if logger:
        logger.finalize()
