"""SAC, coupled training (reference sheeprl/algos/sac/sac.py:32-120 train, :82 main).

TPU-first structure: per iteration the replay buffer is sampled ONCE for all gradient
steps (G x B batch), moved to HBM, and a single jitted call `lax.scan`s over the G
minibatches — critic update, conditional target-EMA, actor update, alpha update per
step. The reference's per-minibatch Python loop with three backward passes becomes one
fused XLA program; the alpha-grad all-reduce (reference sac.py:73) happens implicitly
through the sharded batch.
"""

from __future__ import annotations

import os
import warnings
from math import prod
from typing import Any, Dict, NamedTuple

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.algos.sac.agent import (
    SACParams,
    action_scale_bias,
    actor_action_and_log_prob,
    build_agent,
    ensemble_q_values,
)
from sheeprl_tpu.algos.sac.loss import critic_loss, entropy_loss, policy_loss
from sheeprl_tpu.algos.sac.utils import prepare_obs, test
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.core import failpoints
from sheeprl_tpu.core import health as health_mod
from sheeprl_tpu.core import resilience
from sheeprl_tpu.envs import ingraph as ingraph_envs
from sheeprl_tpu.core.pipeline import AsyncEnvStepper, pipeline_enabled
from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.factory import make_replay_ring
from sheeprl_tpu.data.prefetch import DevicePrefetcher
from sheeprl_tpu.telemetry import device as tel_device
from sheeprl_tpu.telemetry import programs as tel_programs
from sheeprl_tpu.utils.env import finished_episodes, make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.profiler import TraceProfiler
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import PlayerParamsSync, Ratio, polyak_update, save_configs


class SACOptStates(NamedTuple):
    qf: Any
    actor: Any
    alpha: Any


def make_update_core(actor, critic, cfg, runtime, action_scale, action_bias, target_entropy, ema_every: int):
    """The SAC gradient-step core: ``(init_opt, single_update)``.

    ``single_update`` is the unjitted scan-body update (critic + conditional
    target-EMA + actor + alpha on one minibatch). The host train step scans it
    over a prefetched ``[G, B]`` batch stack; the fused in-graph path
    (:func:`make_ingraph_step_fns`) runs the SAME closure inside its
    whole-iteration program, sampling each minibatch from the HBM replay ring —
    one definition, so the two paths cannot drift.
    """
    n_critics = int(cfg.algo.critic.n)
    gamma = float(cfg.algo.gamma)
    tau = float(cfg.algo.tau)
    data_sharding = NamedSharding(runtime.mesh, P("data"))
    qf_tx = instantiate(dict(cfg.algo.critic.optimizer))()
    actor_tx = instantiate(dict(cfg.algo.actor.optimizer))()
    alpha_tx = instantiate(dict(cfg.algo.alpha.optimizer))()

    def init_opt(params: SACParams) -> SACOptStates:
        return SACOptStates(
            qf=qf_tx.init(params.critics),
            actor=actor_tx.init(params.actor),
            alpha=alpha_tx.init(params.log_alpha),
        )

    def single_update(carry, inp):
        params, opt_states, update_idx = carry
        batch, key = inp
        batch = jax.tree_util.tree_map(
            lambda v: jax.lax.with_sharding_constraint(v, data_sharding), batch
        )
        k_next, k_actor = jax.random.split(key)
        alpha = jnp.exp(params.log_alpha)

        # ---- critic update (Eq. 5): target from next actions under current policy
        mean, log_std = actor.apply(params.actor, batch["next_observations"])
        next_actions, next_logp = actor_action_and_log_prob(mean, log_std, k_next, action_scale, action_bias)
        next_q = ensemble_q_values(critic, params.target_critics, batch["next_observations"], next_actions)
        min_next_q = jnp.min(next_q, axis=-1, keepdims=True) - alpha * next_logp
        target_q = batch["rewards"] + (1 - batch["terminated"]) * gamma * min_next_q
        target_q = jax.lax.stop_gradient(target_q)

        def qf_loss_fn(critics_params):
            qs = ensemble_q_values(critic, critics_params, batch["observations"], batch["actions"])
            return critic_loss(qs, target_q, n_critics)

        qf_l, qf_grads = jax.value_and_grad(qf_loss_fn)(params.critics)
        qf_updates, qf_opt = qf_tx.update(qf_grads, opt_states.qf, params.critics)
        new_critics = optax.apply_updates(params.critics, qf_updates)

        # ---- target EMA every `ema_every` updates (reference sac.py:55-56)
        new_targets = jax.lax.cond(
            update_idx % ema_every == 0,
            lambda tgt: polyak_update(new_critics, tgt, tau),
            lambda tgt: tgt,
            params.target_critics,
        )

        # ---- actor update (Eq. 7)
        def actor_loss_fn(actor_params):
            m, ls = actor.apply(actor_params, batch["observations"])
            acts, logp = actor_action_and_log_prob(m, ls, k_actor, action_scale, action_bias)
            qs = ensemble_q_values(critic, new_critics, batch["observations"], acts)
            min_q = jnp.min(qs, axis=-1, keepdims=True)
            return policy_loss(alpha, logp, min_q), logp

        (actor_l, logp), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(params.actor)
        actor_updates, actor_opt = actor_tx.update(actor_grads, opt_states.actor, params.actor)
        new_actor = optax.apply_updates(params.actor, actor_updates)

        # ---- alpha update (Eq. 17)
        def alpha_loss_fn(log_alpha):
            return entropy_loss(log_alpha, jax.lax.stop_gradient(logp), target_entropy)

        alpha_l, alpha_grads = jax.value_and_grad(alpha_loss_fn)(params.log_alpha)
        alpha_updates, alpha_opt = alpha_tx.update(alpha_grads, opt_states.alpha, params.log_alpha)
        new_log_alpha = optax.apply_updates(params.log_alpha, alpha_updates)

        new_params = SACParams(
            actor=new_actor, critics=new_critics, target_critics=new_targets, log_alpha=new_log_alpha
        )
        new_opt = SACOptStates(qf=qf_opt, actor=actor_opt, alpha=alpha_opt)
        return (new_params, new_opt, update_idx + 1), jnp.stack([qf_l, actor_l, alpha_l])

    return init_opt, single_update


def make_train_fn(
    actor, critic, cfg, runtime, action_scale, action_bias, target_entropy, ema_every: int, params_sync=None
):
    if int(cfg.algo.get("grad_microbatches", 1) or 1) > 1:
        # SAC's per-gradient-step batch is already tiny (one replay sample per
        # update in the G-step scan) — no bucketed accumulation to overlap
        warnings.warn(
            "algo.grad_microbatches > 1 is not supported by SAC; falling back to 1"
        )
    init_opt, single_update = make_update_core(
        actor, critic, cfg, runtime, action_scale, action_bias, target_entropy, ema_every
    )

    def train(params, opt_states, batches, key, update_start):
        g = next(iter(batches.values())).shape[0]
        keys = jax.random.split(key, g)
        (params, opt_states, update_end), losses = jax.lax.scan(
            single_update, (params, opt_states, update_start), (batches, keys)
        )
        mean_losses = losses.mean(axis=0)
        # flatten the actor for the player refresh INSIDE the jitted step: one
        # cross-backend transfer instead of a per-leaf round-trip storm (see
        # PlayerParamsSync)
        flat_actor = params_sync.ravel(params.actor) if params_sync is not None else None
        return params, opt_states, update_end, flat_actor, {
            "Loss/value_loss": mean_losses[0],
            "Loss/policy_loss": mean_losses[1],
            "Loss/alpha_loss": mean_losses[2],
        }

    return init_opt, jax_compile.guarded_jit(train, name="sac.train", donate_argnums=(0, 1))


def make_ingraph_step_fns(
    actor,
    critic,
    cfg,
    runtime,
    venv,
    ring,
    action_scale,
    action_bias,
    target_entropy,
    ema_every: int,
    params_sync,
    collect_steps: int,
    batch_size: int,
):
    """The two jitted entry points of the fused in-graph SAC iteration.

    ``prefill_fn(ring_state, carry)`` scans ``collect_steps`` uniform-action env
    steps and scatters the rows into the HBM replay ring — the pre-
    ``learning_starts`` warm-up, entirely on device.

    ``train_fn(params, opt_states, update_counter, ring_state, carry, key, g_eff)``
    is the whole iteration in one donated-carry program: a ``collect_steps``-long
    policy rollout written to the ring, then ``g_eff`` gradient steps each
    sampling the ring in-graph and running :func:`make_update_core`'s
    ``single_update``. ``g_eff`` is a TRACED scalar driving a ``fori_loop``, so
    the Ratio's variable grants (and the health sentinel's shrinking backoff)
    never retrace. Only scalar losses, the raveled actor, and the ``[T, B]``
    episode-metric leaves come back to the host.
    """
    init_opt, single_update = make_update_core(
        actor, critic, cfg, runtime, action_scale, action_bias, target_entropy, ema_every
    )
    step_fn = ingraph_envs.autoreset_step(venv.env, venv.env_params)
    act_space = venv.single_action_space
    act_low = jnp.asarray(np.asarray(act_space.low, np.float32))
    act_high = jnp.asarray(np.asarray(act_space.high, np.float32))
    T = int(collect_steps)
    batch_size = int(batch_size)
    Carry = ingraph_envs.Carry

    # single_update closes over params positionally through the scan carry; the
    # collect scan needs the CURRENT actor — a one-slot ref, same pattern as the
    # on-policy collector (envs/ingraph/rollout.py)
    actor_params_ref = [None]

    def policy_action(obs, key):
        mean, log_std = actor.apply(actor_params_ref[0], obs)
        action, _ = actor_action_and_log_prob(mean, log_std, key, action_scale, action_bias)
        return action

    def uniform_action(obs, key):
        # the pre-learning_starts exploration policy (host loop:
        # envs.action_space.sample())
        return jax.random.uniform(
            key, (obs.shape[0],) + act_low.shape, minval=act_low, maxval=act_high
        )

    def scan_steps(carry, sample_action):
        def one_step(carry, _):
            obs = carry.obs
            key, k_act, k_step = jax.random.split(carry.key, 3)
            action = sample_action(obs, k_act)
            step_keys = jax.random.split(k_step, obs.shape[0])
            state, next_obs, reward, done, info = jax.vmap(step_fn)(
                step_keys, carry.state, action
            )
            reward = reward.astype(jnp.float32)
            ep_ret = carry.ep_ret + reward
            ep_len = carry.ep_len + 1
            rows = {
                "observations": obs,
                # true successor obs (pre-reset when the episode ended): the
                # host loop's real_next_obs / final_obs branch, in-graph
                "next_observations": info["terminal_obs"],
                "actions": action,
                "rewards": reward[:, None],
                # truncated episodes still bootstrap through (1 - terminated)
                # in the critic target — same row the host loop stores
                "terminated": info["terminated"].astype(jnp.float32)[:, None],
            }
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
            return new_carry, (rows, step_metrics)

        return jax.lax.scan(one_step, carry, None, length=T)

    def prefill(ring_state, carry):
        carry, (rows, metrics) = scan_steps(carry, uniform_action)
        return ring.write(ring_state, rows), carry, metrics

    def train(params, opt_states, update_counter, ring_state, carry, key, g_eff):
        actor_params_ref[0] = params.actor
        carry, (rows, metrics) = scan_steps(carry, policy_action)
        ring_state = ring.write(ring_state, rows)

        def update_body(i, acc):
            p, o, uc, loss_sum = acc
            k_samp, k_upd = jax.random.split(jax.random.fold_in(key, i))
            batch = ring.sample(ring_state, k_samp, batch_size)
            (p, o, uc), losses = single_update((p, o, uc), (batch, k_upd))
            return (p, o, uc, loss_sum + losses)

        params, opt_states, update_counter, loss_sum = jax.lax.fori_loop(
            0,
            g_eff,
            update_body,
            (params, opt_states, update_counter, jnp.zeros((3,), jnp.float32)),
        )
        mean_losses = loss_sum / jnp.maximum(g_eff, 1).astype(jnp.float32)
        flat_actor = params_sync.ravel(params.actor)
        train_metrics = {
            "Loss/value_loss": mean_losses[0],
            "Loss/policy_loss": mean_losses[1],
            "Loss/alpha_loss": mean_losses[2],
        }
        return params, opt_states, update_counter, ring_state, carry, flat_actor, metrics, train_metrics

    prefill_fn = jax_compile.guarded_jit(
        prefill, name="sac.ingraph_prefill", donate_argnums=(0, 1)
    )
    train_fn = jax_compile.guarded_jit(
        train, name="sac.ingraph_train", donate_argnums=(0, 1, 2, 3, 4)
    )
    return init_opt, prefill_fn, train_fn


def _main_ingraph(runtime, cfg: Dict[str, Any]):
    """SAC on the in-graph env backend: the whole iteration — a T-step policy
    rollout scanned through the vmapped envs, the replay-ring write, and the
    Ratio's grant of gradient steps sampling that ring — is ONE donated-carry
    jitted program (``sac.ingraph_train``). Transitions never leave HBM:
    buffer-write to gradient-step without a host copy, the off-policy
    counterpart of the fused PPO/A2C path (envs/ingraph/fused.py).

    Single-controller, single-device by design: the replay ring is one donated
    pytree and SAC's minibatches are tiny (a [256, obs] gather), so there is no
    batch axis worth sharding the way the on-policy fused step shards its env
    batch. The ring is NOT checkpointed — on resume (and after a health
    rollback the ring simply keeps its rows) the warm-up scan refills it with
    uniform-action transitions, the same distribution the initial prefill used.
    """
    if runtime.world_size > 1:
        raise ValueError(
            "env.backend=ingraph SAC is single-controller/single-device; "
            "use the gym backend (host replay buffer) for multi-device runs"
        )
    if not ingraph_envs.fused_enabled(cfg):
        raise ValueError(
            "env.backend=ingraph SAC always runs the fused iteration (there is "
            "no split host loop over a device ring); remove env.fused=False"
        )

    state = None
    if cfg.checkpoint.resume_from:
        from sheeprl_tpu.utils.checkpoint import load_state

        state = load_state(cfg.checkpoint.resume_from)

    if len(cfg.algo.cnn_keys.encoder) > 0:
        warnings.warn("SAC algorithm cannot allow to use images as observations, the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

    logger = get_logger(runtime, cfg)
    if logger:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.logger = logger
    runtime.print(f"Log dir: {log_dir}")
    if runtime.is_global_zero and log_dir:
        # compiled-program ledger for this run (parent-pinned env path wins)
        tel_programs.configure_default(os.path.join(log_dir, "telemetry", "programs.jsonl"))

    sentinel = health_mod.HealthSentinel(
        cfg, log_dir=log_dir if runtime.is_global_zero else None, world_size=1
    )
    n_envs = int(cfg.env.num_envs)
    venv = ingraph_envs.make_vector_env(cfg, n_envs, cfg.seed, device=runtime.device)
    action_space = venv.single_action_space
    observation_space = venv.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")

    actor, critic, params, player = build_agent(
        runtime, cfg, observation_space, action_space, state["agent"] if state else None
    )
    # policy forward happens inside the collect scan on the accelerator, not on
    # the host player device build_agent placed the params on
    player.params = jax.device_put(player.params, runtime.device)
    act_dim = prod(action_space.shape)
    obs_dim = prod(observation_space[venv.obs_key].shape)
    target_entropy = jnp.float32(-act_dim)
    action_scale, action_bias = action_scale_bias(action_space.low, action_space.high)

    T = max(1, int(cfg.algo.get("ingraph_collect_steps", 64)))
    policy_steps_per_iter = n_envs * T
    if state:
        cfg.algo.per_rank_batch_size = state["batch_size"]
    batch_size = int(cfg.algo.per_rank_batch_size)
    # EMA cadence counts gradient steps exactly like the host loop (whose
    # iteration advances n_envs policy steps)
    ema_every = int(cfg.algo.critic.target_network_frequency) // n_envs + 1
    params_sync = PlayerParamsSync(player.params)

    ring = make_replay_ring(
        cfg,
        n_envs,
        {
            "observations": ((obs_dim,), jnp.float32),
            "next_observations": ((obs_dim,), jnp.float32),
            "actions": ((act_dim,), jnp.float32),
            "rewards": ((1,), jnp.float32),
            "terminated": ((1,), jnp.float32),
        },
    )
    ring_state = ring.init_state(device=runtime.device)
    init_opt, prefill_fn, train_fn = make_ingraph_step_fns(
        actor,
        critic,
        cfg,
        runtime,
        venv,
        ring,
        action_scale,
        action_bias,
        target_entropy,
        ema_every,
        params_sync,
        T,
        batch_size,
    )
    player.params = params_sync.pull(jax.jit(params_sync.ravel)(params.actor), runtime.device)
    opt_states = init_opt(params)
    if state:
        opt_states = jax.tree_util.tree_map(jnp.asarray, state["opt_states"])
    opt_states = runtime.place_params(opt_states)
    update_counter = jnp.int32(state["update_counter"]) if state else jnp.int32(0)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(cfg.metric.aggregator)

    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter)
    prefill_iters = max(1, int(cfg.algo.learning_starts) // policy_steps_per_iter)
    if cfg.dry_run:
        prefill_iters = 1
        total_iters = 2  # one prefill + one fused train call
    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state:
        ratio.load_state_dict(state["ratio"])

    start_iter = state["iter_num"] + 1 if state else 1
    policy_step = (start_iter - 1) * policy_steps_per_iter
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    last_train = 0
    train_step = 0
    # grad-steps (train_step) advance by the ratio grant, so MFU needs the
    # number of fused-program invocations to recover the per-call wall time
    train_calls = 0
    last_train_calls = 0
    cumulative_grad_steps = 0
    # the ring is not checkpointed: a resumed run re-warms it with
    # prefill_iters of uniform-action transitions before training resumes
    prefill_remaining = prefill_iters
    prefill_policy_steps = prefill_iters * policy_steps_per_iter

    rng = jax.random.PRNGKey(cfg.seed)
    venv.reset(seed=cfg.seed)

    # ----- AOT warmup (core/compile.py): both fused entry points compile on a
    # background thread against the live carry/ring placements, so the first
    # call of each executes a pre-built executable (Compile/retraces stays 0)
    warmup = jax_compile.AOTWarmup(enabled=jax_compile.aot_enabled(cfg))
    if warmup.enabled:
        warmup.add(
            prefill_fn, jax_compile.specs_of(ring_state), jax_compile.specs_of(venv.carry)
        )
        warmup.add(
            train_fn,
            jax_compile.specs_of(params),
            jax_compile.specs_of(opt_states),
            jax_compile.spec_like(update_counter),
            jax_compile.specs_of(ring_state),
            jax_compile.specs_of(venv.carry),
            jax_compile.spec_like(rng),
            jax.ShapeDtypeStruct((), jnp.int32),
        )
        if aggregator is not None:
            warmup.add_task(
                lambda: aggregator.precompile_drain(
                    ("Loss/value_loss", "Loss/policy_loss", "Loss/alpha_loss"),
                    sharding=runtime.replicated,
                )
            )
        warmup.start()

    profiler = TraceProfiler(cfg.metric.get("profiler"), log_dir if runtime.is_global_zero else None)
    train_metrics = None

    def _drain_ingraph_episodes(roll_metrics):
        # the [T, B] episode-metric pull is the ONLY bulk host traffic of an
        # iteration; skip it outright when nothing consumes it (same sampled-
        # at-drain semantics as the fused PPO/A2C loops)
        if cfg.metric.log_level <= 0 or aggregator is None or aggregator.disabled:
            return
        if policy_step - last_log < cfg.metric.log_every and iter_num != total_iters:
            return
        for ep_rew, ep_len in ingraph_envs.iter_finished_episodes(roll_metrics):
            if "Rewards/rew_avg" in aggregator:
                aggregator.update("Rewards/rew_avg", ep_rew)
            if "Game/ep_len_avg" in aggregator:
                aggregator.update("Game/ep_len_avg", ep_len)
            runtime.print(f"Rank-0: policy_step={policy_step}, episode_reward={ep_rew}")

    for iter_num in range(start_iter, total_iters + 1):
        profiler.step(policy_step)
        policy_step += policy_steps_per_iter
        if iter_num == start_iter:
            # both fused entry points must be pre-built before their first call
            # or the call itself traces (an AOT fallback counts as a retrace)
            warmup.wait()

        if prefill_remaining > 0:
            prefill_remaining -= 1
            with timer("Time/env_interaction_time", SumMetric()):
                ring_state, carry, roll_metrics = prefill_fn(ring_state, venv.carry)
                venv.carry = carry
                if not timer.disabled:
                    jax.block_until_ready(carry.obs)
        else:
            # chaos seam first, so drills and the sentinel's rollback ladder
            # cover the fused path too
            failpoints.failpoint("train.fused_update", iter=iter_num)
            g = ratio(policy_step - prefill_policy_steps)
            if g > 0 and sentinel.ratio_scale < 1.0:
                # health-sentinel backoff: shrink this iteration's grant (the
                # dropped steps are spent, not deferred). g stays a TRACED
                # operand of the fused step, so the shrink never retraces.
                g = max(1, int(g * sentinel.ratio_scale))
            with timer("Time/train_time", SumMetric()):
                rng, train_key = jax.random.split(rng)
                (
                    params,
                    opt_states,
                    update_counter,
                    ring_state,
                    carry,
                    flat_actor,
                    roll_metrics,
                    train_metrics,
                ) = train_fn(
                    params,
                    opt_states,
                    update_counter,
                    ring_state,
                    venv.carry,
                    train_key,
                    jnp.int32(g),
                )
                venv.carry = carry
                player.params = params_sync.pull(flat_actor, runtime.device)
                if not timer.disabled:
                    jax.block_until_ready(flat_actor)
            train_step += g
            train_calls += 1
            cumulative_grad_steps += g

        venv.fire_autoreset_failpoints(roll_metrics["dones"])
        _drain_ingraph_episodes(roll_metrics)

        if cfg.metric.log_level > 0 and policy_step > 0:
            if train_metrics is not None and aggregator:
                aggregator.update_from_device(train_metrics)
            if policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters:
                if cumulative_grad_steps > 0:
                    logger.log_metrics(
                        {"Params/replay_ratio": cumulative_grad_steps / policy_step}, policy_step
                    )
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
                        _mfu = tel_device.mfu(
                            getattr(train_fn, "last_step_flops", None),
                            timer_metrics["Time/train_time"]
                            / max(train_calls - last_train_calls, 1),
                            runtime.device,
                        )
                        if _mfu is not None:
                            logger.log_metrics({"Time/mfu": _mfu}, policy_step)
                    timer.reset()
                last_log = policy_step
                last_train = train_step
                last_train_calls = train_calls

        env_deltas = resilience.drain_env_counters(venv, aggregator)
        jax_compile.drain_compile_counters(aggregator)
        if cumulative_grad_steps > 0 and not jax_compile.is_steady():
            jax_compile.mark_steady()

        action = sentinel.observe(policy_step, train_metrics=train_metrics, env_counters=env_deltas)
        if action.rollback:
            rb_state = sentinel.take_rollback_state(os.path.join(log_dir, "checkpoint"))
            if rb_state is not None:
                params = runtime.place_params(jax.tree_util.tree_map(jnp.asarray, rb_state["agent"]))
                opt_states = runtime.place_params(
                    jax.tree_util.tree_map(jnp.asarray, rb_state["opt_states"])
                )
                update_counter = jnp.int32(rb_state["update_counter"])
                ratio.load_state_dict(rb_state["ratio"])
                # the ring keeps its rows (off-policy data stays valid); only
                # the learner state rewinds to the certified snapshot
                player.params = params_sync.pull(
                    jax.jit(params_sync.ravel)(params.actor), runtime.device
                )
                runtime.print(
                    f"Health rollback at policy_step={policy_step}: restored certified "
                    "checkpoint, training continues."
                )
        sentinel.drain(aggregator)

        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            iter_num == total_iters and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": jax.device_get(params),
                "opt_states": jax.device_get(opt_states),
                "update_counter": int(update_counter),
                "ratio": ratio.state_dict(),
                "iter_num": iter_num,
                "batch_size": cfg.algo.per_rank_batch_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{runtime.global_rank}.ckpt")
            runtime.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path,
                state=ckpt_state,
                healthy=sentinel.certifiable,
                policy_step=policy_step,
            )

    profiler.close()
    venv.close()
    if runtime.is_global_zero and cfg.algo.run_test:
        obs_key = venv.obs_key

        class _EvalPlayer:
            # adapt SACPlayer (flat-obs, action-only return) to the dict-obs
            # (actions, key) protocol the shared ingraph greedy eval drives
            def get_actions(self, obs, key, greedy=False):
                key, sub = jax.random.split(key)
                return player.get_actions(obs[obs_key], sub, greedy=greedy), key

        ingraph_envs.test(_EvalPlayer(), runtime, cfg, log_dir)
    if logger:
        logger.finalize()


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    if ingraph_envs.env_backend(cfg) == "ingraph":
        # in-graph backend: device-resident envs + HBM replay ring, the whole
        # iteration fused into one jitted program — a separate loop shape from
        # the per-step host interaction below
        return _main_ingraph(runtime, cfg)
    if "minedojo" in cfg.env.wrapper._target_.lower():
        raise ValueError("MineDojo is not currently supported by SAC agent.")
    world_size = runtime.world_size

    state = None
    if cfg.checkpoint.resume_from:
        from sheeprl_tpu.utils.checkpoint import load_state

        state = load_state(cfg.checkpoint.resume_from)

    if len(cfg.algo.cnn_keys.encoder) > 0:
        warnings.warn("SAC algorithm cannot allow to use images as observations, the CNN keys will be ignored")
        cfg.algo.cnn_keys.encoder = []

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
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space
    if not isinstance(action_space, gym.spaces.Box):
        raise ValueError("Only continuous action space is supported for the SAC agent")
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if len(cfg.algo.mlp_keys.encoder) == 0:
        raise RuntimeError("You should specify at least one MLP key for the encoder: `mlp_keys.encoder=[state]`")
    for k in cfg.algo.mlp_keys.encoder:
        if len(observation_space[k].shape) > 1:
            raise ValueError(
                "Only environments with vector-only observations are supported by the SAC agent. "
                f"The observation with key '{k}' has shape {observation_space[k].shape}. "
                f"Provided environment: {cfg.env.id}"
            )

    actor, critic, params, player = build_agent(
        runtime, cfg, observation_space, action_space, state["agent"] if state else None
    )
    act_dim = prod(action_space.shape)
    target_entropy = jnp.float32(-act_dim)
    action_scale, action_bias = action_scale_bias(action_space.low, action_space.high)

    policy_steps_per_iter = int(n_envs)
    ema_every = int(cfg.algo.critic.target_network_frequency) // policy_steps_per_iter + 1
    params_sync = PlayerParamsSync(player.params)
    init_opt, train_fn = make_train_fn(
        actor, critic, cfg, runtime, action_scale, action_bias, target_entropy, ema_every, params_sync
    )
    # the host player must never hold mesh-resident params: its action pulls would
    # fail/pay per-leaf round-trips, and player_sync_every>1 defers the first refresh
    player.params = params_sync.pull(jax.jit(params_sync.ravel)(params.actor), runtime.player_device)
    opt_states = init_opt(params)
    if state:
        opt_states = jax.tree_util.tree_map(jnp.asarray, state["opt_states"])
    opt_states = runtime.place_params(opt_states)
    update_counter = jnp.int32(state["update_counter"]) if state else jnp.int32(0)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(cfg.metric.aggregator)

    buffer_size = cfg.buffer.size // n_envs if not cfg.dry_run else 1
    if bool(cfg.buffer.get("device", False)):
        raise ValueError(
            "buffer.device=True is currently supported by the Dreamer-family loops "
            "only; use the host buffer here"
        )
    rb = ReplayBuffer(
        buffer_size,
        n_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir, "memmap_buffer", f"rank_{runtime.global_rank}"),
        obs_keys=("observations",),
    )
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])

    last_train = 0
    train_step = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state else 1
    policy_step = state["iter_num"] * cfg.env.num_envs if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if state:
        cfg.algo.per_rank_batch_size = state["batch_size"] // world_size
        learning_starts += start_iter
        prefill_steps += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    player_sync_every = max(1, int(cfg.algo.get("player_sync_every", 1)))
    train_every = max(1, int(cfg.algo.get("train_every", 1)))
    if state:
        ratio.load_state_dict(state["ratio"])

    def sample_batches(g: int):
        bs = cfg.algo.per_rank_batch_size * world_size
        sample = rb.sample(batch_size=g * bs, sample_next_obs=cfg.buffer.sample_next_obs)
        return {k: np.asarray(v, dtype=np.float32).reshape(g, bs, *v.shape[2:]) for k, v in sample.items()}

    # Double-buffered host->HBM pipeline (see sheeprl_tpu/data/prefetch.py): the
    # [G, B] batch for the next train call transfers while the chip is still busy.
    prefetcher = DevicePrefetcher(
        sample_batches,
        device=NamedSharding(runtime.mesh, P(None, "data")),
        chunk=int(cfg.buffer.get("prefetch_batches", 1)),
        chunk_key="g",
    )

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter})."
        )
    if cfg.checkpoint.every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The checkpoint.every parameter ({cfg.checkpoint.every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter})."
        )

    profiler = TraceProfiler(cfg.metric.get("profiler"), log_dir if runtime.is_global_zero else None)
    rng = jax.random.PRNGKey(cfg.seed)
    # rollout randomness lives on the PLAYER device: feeding mesh-resident keys/obs
    # into the host player's jit would silently move the policy step onto the
    # accelerator and pay a synchronous round-trip per env step
    player_rng = jax.device_put(jax.random.PRNGKey(cfg.seed + 1), runtime.player_device)
    mlp_keys = cfg.algo.mlp_keys.encoder
    cumulative_grad_steps = 0

    last_flat_actor = None
    train_calls = 0
    obs = envs.reset(seed=cfg.seed)[0]
    obs_vec = np.concatenate([np.asarray(obs[k], dtype=np.float32).reshape(n_envs, -1) for k in mlp_keys], -1)
    # software pipeline (core/pipeline.py): the env workers step while the chip
    # runs the training phase below — the prefetcher already samples one train
    # call behind, so training never depended on the in-flight row anyway
    stepper = AsyncEnvStepper(envs, enabled=pipeline_enabled(cfg))

    for iter_num in range(start_iter, total_iters + 1):
        profiler.step(policy_step)
        policy_step += n_envs

        with timer("Time/env_interaction_time", SumMetric()):
            if iter_num < learning_starts:
                actions = envs.action_space.sample()
            else:
                player_rng, act_key = jax.random.split(player_rng)
                # SAC's obs is a single flat vector: one small put per step (the
                # PPO-style packed codec would be the same single transfer)
                actions = np.asarray(
                    player.get_actions(jax.device_put(obs_vec, runtime.player_device), act_key)
                )
            stepper.step_async(actions.reshape(envs.action_space.shape))

        env_step_done = False

        def _finish_env_step():
            nonlocal env_step_done, obs_vec
            if env_step_done:
                return
            env_step_done = True
            with timer("Time/env_interaction_time", SumMetric()):
                next_obs, rewards, terminated, truncated, info = stepper.step_wait()
                next_obs_vec = np.concatenate(
                    [np.asarray(next_obs[k], dtype=np.float32).reshape(n_envs, -1) for k in mlp_keys], -1
                )
                # real next obs for terminated envs is in final_obs (SAME_STEP autoreset)
                real_next_obs = next_obs_vec.copy()
                if "final_obs" in info:
                    for idx, fo in enumerate(np.asarray(info["final_obs"], dtype=object)):
                        if fo is not None:
                            real_next_obs[idx] = np.concatenate(
                                [np.asarray(fo[k], dtype=np.float32).reshape(-1) for k in mlp_keys], -1
                            )
            step_data = {
                "terminated": np.asarray(terminated).reshape(1, n_envs, -1).astype(np.uint8),
                "truncated": np.asarray(truncated).reshape(1, n_envs, -1).astype(np.uint8),
                "actions": np.asarray(actions).reshape(1, n_envs, -1).astype(np.float32),
                "observations": obs_vec[np.newaxis],
                "rewards": np.asarray(rewards, dtype=np.float32).reshape(1, n_envs, -1),
            }
            if not cfg.buffer.sample_next_obs:
                step_data["next_observations"] = real_next_obs[np.newaxis]
            with prefetcher.guard():  # no torn rows under the worker's concurrent sample
                rb.add(step_data, validate_args=cfg.buffer.validate_args)
            obs_vec = next_obs_vec
            if cfg.metric.log_level > 0:
                for i, (ep_rew, ep_len) in enumerate(finished_episodes(info)):
                    if aggregator and "Rewards/rew_avg" in aggregator:
                        aggregator.update("Rewards/rew_avg", ep_rew)
                    if aggregator and "Game/ep_len_avg" in aggregator:
                        aggregator.update("Game/ep_len_avg", ep_len)
                    runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep_rew}")

        if not rb.full and getattr(rb, "_pos", 0) < 2:
            # too few stored rows to sample from: complete the env step serially
            # before the first train calls (startup edge only)
            _finish_env_step()

        # ---- overlap window: env workers step while the chip trains.
        # ``algo.train_every > 1`` batches several iterations' gradient steps into
        # one jitted call (Ratio keeps the step accounting exact): on remote
        # accelerators every dispatched program costs fixed round-trip overhead,
        # so fusing N iterations' updates divides that overhead by N at the
        # price of params being up to N-1 env steps staler for replay writes.
        if iter_num >= learning_starts and (
            train_every <= 1 or iter_num % train_every == 0 or iter_num == total_iters
        ):
            per_rank_gradient_steps = ratio((policy_step - prefill_steps * n_envs) / world_size)
            if per_rank_gradient_steps > 0 and sentinel.ratio_scale < 1.0:
                # health-sentinel backoff for replay-ratio loops: shrink this
                # iteration's gradient-step grant (the dropped steps are spent,
                # not deferred — a deliberate cooling-off, not bookkeeping)
                per_rank_gradient_steps = max(1, int(per_rank_gradient_steps * sentinel.ratio_scale))
            if per_rank_gradient_steps > 0:
                g = per_rank_gradient_steps
                # prefetched during the previous train step (sample + async device_put
                # overlap compute); kwargs change -> synchronous fallback inside get()
                batches = prefetcher.get(g=g)
                with timer("Time/train_time", SumMetric()):
                    rng, train_key = jax.random.split(rng)
                    params, opt_states, update_counter, flat_actor, train_metrics = train_fn(
                        params, opt_states, batches, train_key, update_counter
                    )
                    # ONE flat cross-backend transfer refreshes the host player; on
                    # remote accelerators cfg.algo.player_sync_every amortizes the
                    # round-trip. The explicit block keeps Time/train_time honest on
                    # locally-attached backends (async dispatch returns instantly).
                    last_flat_actor = flat_actor
                    # cadence counts TRAIN calls (iter_num can skip sync forever
                    # when Ratio grants steps only on a phase-locked subset)
                    train_calls += 1
                    if train_calls % player_sync_every == 0:
                        player.params = params_sync.pull(flat_actor, runtime.player_device)
                    if not timer.disabled:
                        # fence ONLY when timing: Time/train_time must include the
                        # device work, but an unconditional per-iteration sync would
                        # serialize the loop on the dispatch round-trip
                        jax.block_until_ready(flat_actor)
                    cumulative_grad_steps += g
                train_step += world_size * g

        _finish_env_step()

        if cfg.metric.log_level > 0 and policy_step > 0:
            if iter_num >= learning_starts and "train_metrics" in dir():
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
                if cumulative_grad_steps > 0:
                    logger.log_metrics(
                        {"Params/replay_ratio": cumulative_grad_steps * world_size / policy_step}, policy_step
                    )
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

        env_deltas = resilience.drain_env_counters(envs, aggregator)
        jax_compile.drain_compile_counters(aggregator)
        if cumulative_grad_steps > 0 and not jax_compile.is_steady():
            # everything reachable has compiled once: later traces are drift
            jax_compile.mark_steady()

        # ----- health sentinel: warn -> backoff (ratio grant above) -> rollback
        action = sentinel.observe(
            policy_step,
            train_metrics=train_metrics if iter_num >= learning_starts and "train_metrics" in dir() else None,
            env_counters=env_deltas,
        )
        if action.rollback:
            rb_state = sentinel.take_rollback_state(os.path.join(log_dir, "checkpoint"))
            if rb_state is not None:
                params = runtime.place_params(
                    jax.tree_util.tree_map(jnp.asarray, rb_state["agent"])
                )
                opt_states = runtime.place_params(
                    jax.tree_util.tree_map(jnp.asarray, rb_state["opt_states"])
                )
                update_counter = jnp.int32(rb_state["update_counter"])
                ratio.load_state_dict(rb_state["ratio"])
                # the replay buffer keeps its rows (off-policy data stays valid);
                # only the learner state rewinds to the certified snapshot
                player.params = params_sync.pull(
                    params_sync.ravel(params.actor), runtime.player_device
                )
                last_flat_actor = None
                runtime.print(
                    f"Health rollback at policy_step={policy_step}: restored certified "
                    "checkpoint, training continues."
                )
        sentinel.drain(aggregator)

        if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
            iter_num == total_iters and cfg.checkpoint.save_last
        ):
            last_checkpoint = policy_step
            ckpt_state = {
                "agent": jax.device_get(params),
                "opt_states": jax.device_get(opt_states),
                "update_counter": int(update_counter),
                "ratio": ratio.state_dict(),
                "iter_num": iter_num * world_size,
                "batch_size": cfg.algo.per_rank_batch_size * world_size,
                "last_log": last_log,
                "last_checkpoint": last_checkpoint,
            }
            ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{runtime.global_rank}.ckpt")
            runtime.call(
                "on_checkpoint_coupled",
                ckpt_path=ckpt_path,
                state=ckpt_state,
                replay_buffer=rb if cfg.buffer.checkpoint else None,
                io_lock=prefetcher.guard(),
                healthy=sentinel.certifiable,
                policy_step=policy_step,
            )

    prefetcher.close()
    profiler.close()
    envs.close()
    if last_flat_actor is not None:
        # final refresh: player_sync_every may have skipped the last iterations,
        # and test()/model registration must see the final policy
        player.params = params_sync.pull(last_flat_actor, runtime.player_device)
    if runtime.is_global_zero and cfg.algo.run_test:
        test(player, runtime, cfg, log_dir)
    if logger:
        logger.finalize()
