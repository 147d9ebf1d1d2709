"""A2C, coupled training (reference sheeprl/algos/a2c/a2c.py:26-118 train, :118 main).

Same rollout skeleton as PPO; the optimization phase is one jitted call that
accumulates gradients across minibatches (`lax.scan`) and applies a single optimizer
step — the in-graph equivalent of the reference's `fabric.no_backward_sync`
gradient-accumulation loop.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.algos.a2c.loss import policy_loss, value_loss
from sheeprl_tpu.algos.a2c.utils import normalize_obs, prepare_obs, test
from sheeprl_tpu.algos.ppo.agent import build_agent, evaluate_actions
from sheeprl_tpu.algos.ppo.loss import entropy_loss
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.core import failpoints
from sheeprl_tpu.core import health as health_mod
from sheeprl_tpu.core import resilience
from sheeprl_tpu.core.pipeline import AsyncEnvStepper, PackedObsCodec, pipeline_enabled
from sheeprl_tpu.data.factory import make_rollout_buffer
from sheeprl_tpu.envs import ingraph as ingraph_envs
from sheeprl_tpu.parallel import handoff, overlap
from sheeprl_tpu.telemetry import device as tel_device
from sheeprl_tpu.telemetry import programs as tel_programs
from sheeprl_tpu.telemetry import trace
from sheeprl_tpu.utils.env import finished_episodes, make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.optim import with_clipping
from sheeprl_tpu.utils.profiler import TraceProfiler
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import PlayerParamsSync, gae, normalize_tensor, save_configs


def make_update_impl(
    agent, tx, cfg, runtime, n_data: int, obs_keys, params_sync=None, *, axis_name=None, shards=1, constrain_data=True, batch_size=None
):
    """Build the raw (unjitted) per-iteration optimization function.

    Same two flavors as :func:`sheeprl_tpu.algos.ppo.ppo.make_update_impl`:
    the default is the jitted split-path train step AND the single-device fused
    iteration's update phase; ``axis_name="data"``/``shards=N`` is the
    shard-local body for the fused ``shard_map`` variant — the accumulated
    gradient (and the ``pg_sum``/``v_sum``/``gnorm`` scalars feeding the
    nonfinite guard's decision, so every shard takes the identical
    apply-or-skip branch) all-reduce via ``jax.lax.pmean`` before the single
    optimizer step.
    """
    # batch_size overrides the data-parallel global batch for the population
    # trainer's member-sharded mesh (see the PPO twin)
    global_bs = (
        int(batch_size) if batch_size is not None
        else int(cfg.algo.per_rank_batch_size) * runtime.world_size
    )
    shards = int(shards)
    local_n = n_data // shards
    local_bs = max(global_bs // shards, 1)
    n_minibatches = max(local_n // local_bs, 1)
    # constrain_data=False: see the PPO twin — the population trainer vmaps
    # this body over a member axis where the env-batch constraint is invalid.
    data_sharding = (
        NamedSharding(runtime.mesh, P("data")) if (axis_name is None and constrain_data) else None
    )
    nonfinite_guard = resilience.guard_enabled(resilience.resolve(cfg))

    def loss_fn(params, batch):
        norm_obs = normalize_obs(batch, [], obs_keys)
        actions = (
            jnp.split(batch["actions"], np.cumsum(agent.actions_dim)[:-1].tolist(), axis=-1)
            if len(agent.actions_dim) > 1
            else [batch["actions"]]
        )
        actor_outs, new_values = agent.apply(params, norm_obs)
        logprobs, entropy = evaluate_actions(actor_outs, actions, agent.is_continuous, agent.distribution)
        advantages = batch["advantages"]
        if cfg.algo.normalize_advantages:
            advantages = normalize_tensor(advantages)
        pg_loss = policy_loss(logprobs, advantages, cfg.algo.loss_reduction)
        v_loss = value_loss(new_values, batch["returns"], cfg.algo.loss_reduction)
        ent_loss = entropy_loss(entropy, cfg.algo.loss_reduction)
        total = pg_loss + cfg.algo.vf_coef * v_loss + cfg.algo.ent_coef * ent_loss
        return total, (pg_loss, v_loss)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    micro = overlap.microbatches(cfg)
    # gradient-sync overlap (parallel/overlap.py): with micro > 1 each
    # minibatch's gradient is computed chunk-by-chunk with a per-bucket psum,
    # so the returned per-minibatch gradient is ALREADY axis-averaged — the
    # single post-scan pmean below must then be skipped for grads (the scalar
    # sums still reduce once). micro == 1 keeps the op-identical reference
    # path: local grads accumulated, ONE pmean at the end.
    inner_axis = axis_name if micro > 1 else None

    def train(params, opt_state, data, next_values, key, lr_scale):
        returns, advantages = gae(
            data["rewards"],
            data["values"],
            data["dones"],
            next_values,
            cfg.algo.rollout_steps,
            cfg.algo.gamma,
            cfg.algo.gae_lambda,
        )
        data = dict(data)
        data["returns"] = returns
        data["advantages"] = advantages
        flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in data.items()}
        if n_minibatches == 1 and local_bs >= local_n:
            # ONE minibatch covering every row: a permutation only reorders the
            # batch mean, so skip the O(N log N) sort and the full-data gather
            perm = None
        else:
            n_keep = n_minibatches * local_bs
            perm = jax.random.permutation(key, local_n)[:n_keep].reshape(n_minibatches, local_bs)

        def accumulate(carry, idx):
            grads_acc, pg_acc, v_acc = carry
            if idx is None:
                batch = flat
                if data_sharding is not None:
                    batch = jax.tree_util.tree_map(
                        lambda v: jax.lax.with_sharding_constraint(v, data_sharding), batch
                    )
            elif data_sharding is not None:
                batch = jax.tree_util.tree_map(
                    lambda v: jax.lax.with_sharding_constraint(jnp.take(v, idx, axis=0), data_sharding), flat
                )
            else:
                # shard-local body: the rows are already this shard's block
                batch = jax.tree_util.tree_map(lambda v: jnp.take(v, idx, axis=0), flat)
            (_, (pg, vl)), grads = overlap.accumulate_grads(
                grad_fn, params, batch,
                microbatches=micro, axis_name=inner_axis, axis_size=shards,
            )
            grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
            return (grads_acc, pg_acc + pg, v_acc + vl), None

        zero_grads = jax.tree_util.tree_map(jnp.zeros_like, params)
        (grads, pg_sum, v_sum), _ = jax.lax.scan(
            accumulate, (zero_grads, jnp.float32(0), jnp.float32(0)), perm,
            length=1 if perm is None else None,
        )
        if axis_name is not None:
            # data-parallel all-reduce of the ONE accumulated update; the loss
            # sums reduce too so the finite_or_skip decision below is
            # replicated (a shard-local skip would fork the param replicas).
            # With microbatching the grads already all-reduced per bucket
            # inside accumulate_grads — only the scalars remain.
            if inner_axis is None:
                grads = jax.lax.pmean(grads, axis_name)
            pg_sum = jax.lax.pmean(pg_sum, axis_name)
            v_sum = jax.lax.pmean(v_sum, axis_name)
        gnorm = optax.global_norm(grads)
        updates, new_opt_state = tx.update(grads, opt_state, params)
        # health-sentinel LR backoff: traced scalar operand; 1.0 is IEEE-exact
        updates = jax.tree_util.tree_map(lambda u: u * lr_scale, updates)
        new_params = optax.apply_updates(params, updates)
        if nonfinite_guard:
            # one accumulated update per iteration: guard that single apply
            (params, opt_state), skipped = resilience.finite_or_skip(
                (pg_sum, v_sum, gnorm), (new_params, new_opt_state), (params, opt_state)
            )
        else:
            params, opt_state, skipped = new_params, new_opt_state, jnp.float32(0.0)
        flat_params = params_sync.ravel(params) if params_sync is not None else jnp.zeros(())
        return params, opt_state, flat_params, {
            "Loss/policy_loss": pg_sum / n_minibatches,
            "Loss/value_loss": v_sum / n_minibatches,
            "Resilience/nonfinite_skips": skipped,
            "Grads/global_norm": gnorm,
        }

    return train


def make_train_fn(agent, tx, cfg, runtime, n_data: int, obs_keys, params_sync=None):
    """The jitted split-path train step (see :func:`make_update_impl`)."""
    train = make_update_impl(agent, tx, cfg, runtime, n_data, obs_keys, params_sync)
    return jax_compile.guarded_jit(train, name="a2c.train", donate_argnums=(0, 1))


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    use_ingraph = ingraph_envs.env_backend(cfg) == "ingraph"
    if len(cfg.algo.cnn_keys.encoder) > 0:
        raise ValueError("A2C is vector-observation only: do not set `algo.cnn_keys.encoder`")
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
    if runtime.is_global_zero and log_dir:
        # compiled-program ledger for this run (parent-pinned env path wins)
        tel_programs.configure_default(os.path.join(log_dir, "telemetry", "programs.jsonl"))

    ft = resilience.resolve(cfg)
    sentinel = health_mod.HealthSentinel(
        cfg, log_dir=log_dir if runtime.is_global_zero else None, world_size=world_size
    )
    n_envs = cfg.env.num_envs * world_size
    if use_ingraph:
        # in-graph backend (envs/ingraph/): the env batch is one device-resident
        # pytree stepped inside the fused rollout scan (see ppo.py for the
        # full rationale — A2C shares the structure)
        collect_device = runtime.device
        envs = ingraph_envs.make_vector_env(cfg, n_envs, cfg.seed, device=collect_device)
    else:
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
    obs_keys = cfg.algo.mlp_keys.encoder

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
    if use_ingraph:
        # policy forward runs inside the scan on the collect device, not on the
        # (host) player device build_agent placed the params on
        player.params = jax.device_put(player.params, collect_device)
    player_sync_device = collect_device if use_ingraph else runtime.player_device

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
    device_rollout = getattr(rb, "backend", "host") == "device"

    last_train = 0
    train_step = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state else 1
    policy_step = state["iter_num"] * cfg.env.num_envs * cfg.algo.rollout_steps if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    policy_steps_per_iter = int(n_envs * cfg.algo.rollout_steps)
    total_iters = cfg.algo.total_steps // policy_steps_per_iter if not cfg.dry_run else 1
    if state:
        cfg.algo.per_rank_batch_size = state["batch_size"] // world_size
    n_data = cfg.algo.rollout_steps * n_envs

    params_sync = PlayerParamsSync(player.params)
    train_fn = make_train_fn(agent, tx, cfg, runtime, n_data, obs_keys, params_sync)
    profiler = TraceProfiler(cfg.metric.get("profiler"), log_dir if runtime.is_global_zero else None)
    rng = jax.random.PRNGKey(cfg.seed)
    player_rng = jax.device_put(jax.random.PRNGKey(cfg.seed + 1), runtime.player_device)
    if state and "rng" in state:
        rng = jnp.asarray(state["rng"])
        player_rng = jax.device_put(jnp.asarray(state["player_rng"]), runtime.player_device)

    step_data = {}
    reset_obs = envs.reset(seed=cfg.seed)[0]
    next_obs = {}
    for k in obs_keys:
        next_obs[k] = reset_obs[k]
        step_data[k] = reset_obs[k][np.newaxis]

    # ----- software pipeline (core/pipeline.py): same structure as ppo.py — env
    # workers step while the host closes out the previous step; obs reach the
    # device as ONE packed put per step with the prior rewards/dones riding along
    stepper = AsyncEnvStepper(envs, enabled=pipeline_enabled(cfg) and not use_ingraph)
    codec = PackedObsCodec(cnn_keys=(), device=runtime.player_device)
    collector = None
    fused_trainer = None
    if use_ingraph:
        # A2C's loss recomputes logprobs, so the collector stores only
        # obs/actions/values/rewards/dones
        collector = ingraph_envs.InGraphRolloutCollector(
            envs,
            player,
            rollout_steps=cfg.algo.rollout_steps,
            gamma=cfg.algo.gamma,
            clip_rewards=cfg.env.clip_rewards,
            store_logprobs=False,
            name="a2c",
        )
        if ingraph_envs.fused_enabled(cfg):
            # ----- whole-iteration fusion (envs/ingraph/fused.py): rollout scan
            # + GAE + the accumulate-and-apply update compile into ONE program;
            # on a multi-device mesh the env batch shards on the `data` axis and
            # the accumulated gradient all-reduces in-graph
            update_impl = make_update_impl(
                agent,
                tx,
                cfg,
                runtime,
                n_data,
                obs_keys,
                params_sync,
                axis_name="data" if world_size > 1 else None,
                shards=world_size,
            )
            fused_trainer = ingraph_envs.FusedInGraphTrainer(
                collector,
                update_impl,
                n_extras=1,
                mesh=runtime.mesh if world_size > 1 else None,
                name="a2c",
            )
            fused_trainer.shard_carry()
    zero_extra = {
        "rewards": np.zeros((n_envs, 1), np.float32),
        "dones": np.zeros((n_envs, 1), np.float32),
    }

    # ----- AOT warmup (core/compile.py): same scheme as ppo.py — compile the
    # packed-act step, the accumulate-and-apply train step, and the metric-drain
    # kernels on a background thread while the first rollout collects.
    warmup = jax_compile.AOTWarmup(enabled=jax_compile.aot_enabled(cfg))
    if warmup.enabled and use_ingraph:
        if fused_trainer is not None:
            # ONE entry point for the whole iteration: collect + GAE + the
            # accumulated update. Specs come from the live (mesh-sharded, for
            # the shard_map variant) params/opt_state/carry.
            warmup.add(
                fused_trainer.step_fn,
                *fused_trainer.warmup_specs(params, opt_state, rng, jnp.float32(1.0)),
            )
        else:
            # ONE rollout entry point (the fused scan); its abstract outputs are
            # the train step's input specs — both derive without touching the
            # device
            warmup.add(collector.collect_fn, *collector.warmup_specs())
            data_specs, nv_spec = collector.output_specs()
            warmup.add(
                train_fn,
                jax_compile.specs_of(params),
                jax_compile.specs_of(opt_state),
                # the handoff assembles the batch PRE-SHARDED on the mesh (env
                # axis): warmup against that layout (see ppo.py)
                handoff.shard_specs(data_specs, runtime.mesh, batch_axis=1),
                jax.ShapeDtypeStruct(nv_spec.shape, jnp.float32, sharding=runtime.replicated),
                jax_compile.spec_like(rng),
                jax.ShapeDtypeStruct((), jnp.float32),
            )
        if aggregator is not None:
            warmup.add_task(
                lambda: aggregator.precompile_drain(
                    (
                        "Loss/policy_loss",
                        "Loss/value_loss",
                        "Resilience/nonfinite_skips",
                        "Grads/global_norm",
                    ),
                    sharding=runtime.replicated,
                ),
                name="metric.drain",
            )
        warmup.start()
    elif warmup.enabled:
        packed0 = codec.encode(next_obs, extra=zero_extra)
        act_fn = player.packed_act_fn(codec)
        act_specs = (
            jax_compile.specs_of(player.params),
            jax_compile.spec_like(packed0),
            jax_compile.spec_like(player_rng),
        )
        warmup.add(act_fn, *act_specs)
        if not device_rollout:
            cat_s, _env_s, _logp_s, val_s, _key_s = jax.eval_shape(act_fn.fun, *act_specs)
            T = int(cfg.algo.rollout_steps)
            data_specs = {
                k: jax.ShapeDtypeStruct((T, *next_obs[k].shape), jnp.float32) for k in obs_keys
            }
            for k, s in (("actions", cat_s), ("values", val_s)):
                data_specs[k] = jax.ShapeDtypeStruct((T, *s.shape), jnp.float32)
            for k in ("rewards", "dones"):
                data_specs[k] = jax.ShapeDtypeStruct((T, n_envs, 1), jnp.float32)
            warmup.add(
                train_fn,
                jax_compile.specs_of(params),
                jax_compile.specs_of(opt_state),
                # host rollout enters the mesh shard-at-put (env axis)
                handoff.shard_specs(data_specs, runtime.mesh, batch_axis=1),
                jax.ShapeDtypeStruct(val_s.shape, jnp.float32),
                jax_compile.spec_like(rng),
                jax.ShapeDtypeStruct((), jnp.float32),
            )
        if aggregator is not None:
            warmup.add_task(
                lambda: aggregator.precompile_drain(
                    (
                        "Loss/policy_loss",
                        "Loss/value_loss",
                        "Resilience/nonfinite_skips",
                        "Grads/global_norm",
                    ),
                    sharding=runtime.replicated,
                ),
                name="metric.drain",
            )
        warmup.start()

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
            rewards = pending["rewards"]
            step_data["dones"] = pending["dones"][np.newaxis]
            step_data["values"] = np.asarray(pending["values"])[np.newaxis]
            step_data["actions"] = np.asarray(pending["cat_actions"])[np.newaxis]
            step_data["rewards"] = rewards[np.newaxis]
            if cfg.buffer.memmap:
                step_data["returns"] = np.zeros_like(rewards, shape=(1, *rewards.shape))
                step_data["advantages"] = np.zeros_like(rewards, shape=(1, *rewards.shape))
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
            "batch_size": cfg.algo.per_rank_batch_size * world_size,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "rng": jax.device_get(rng),
            "player_rng": jax.device_get(player_rng),
        }

    def _drain_ingraph_episodes(roll_metrics):
        """Pull and log the [T, B] episode-metric leaves from an ingraph rollout.

        Skipped when nothing consumes them: aggregator disabled, or between
        ``log_every`` drains (episodes are then sampled at drain iterations
        rather than fetched every iteration) — see ppo.py."""
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

    guard = resilience.PreemptionGuard(
        enabled=ft.preemption.enabled, stop_after_iters=ft.preemption.stop_after_iters
    )
    with guard:
        for iter_num in range(start_iter, total_iters + 1):
            profiler.step(policy_step)
            if fused_trainer is not None:
                # ----- whole-iteration fused step (envs/ingraph/fused.py): the
                # rollout scan, GAE, and the accumulated update run as ONE
                # compiled donated-carry program (see ppo.py)
                failpoints.failpoint("train.fused_update", iter=iter_num)
                failpoints.failpoint(
                    "train.grad_sync", iter=iter_num, microbatches=overlap.microbatches(cfg)
                )
                with trace.span("train/update", fused=True, iter=iter_num), timer(
                    "Time/train_time", SumMetric()
                ):
                    if iter_num == start_iter:
                        warmup.wait()
                    policy_step += n_envs * cfg.algo.rollout_steps
                    rng, train_key = jax.random.split(rng)
                    params, opt_state, flat_params, roll_metrics, train_metrics = fused_trainer.step(
                        params,
                        opt_state,
                        fused_trainer.to_mesh(train_key),
                        fused_trainer.to_mesh(jnp.float32(sentinel.lr_scale)),
                    )
                    player.params = params_sync.pull(flat_params, player_sync_device)
                    if not timer.disabled:  # sync only when the phase is being timed
                        jax.block_until_ready(params)
                train_step += world_size
                envs.fire_autoreset_failpoints(roll_metrics["dones"])
                _drain_ingraph_episodes(roll_metrics)
            elif use_ingraph:
                # ----- split ingraph path (env.fused=False): the fused rollout
                # scan followed by the separately jitted train step below — the
                # fused path's parity reference
                with trace.span("train/collect", iter=iter_num), timer(
                    "Time/env_interaction_time", SumMetric()
                ):
                    policy_step += n_envs * cfg.algo.rollout_steps
                    ingraph_data, roll_metrics, ingraph_next_values = collector.collect()
                # zero-cost unless an env.autoreset drill is armed
                envs.fire_autoreset_failpoints(roll_metrics["dones"])
                _drain_ingraph_episodes(roll_metrics)
            else:
                for _ in range(cfg.algo.rollout_steps):
                    policy_step += n_envs

                    with timer("Time/env_interaction_time", SumMetric()):
                        # ONE packed host->device transfer per step (A2C reuses the
                        # PPO agent, vector obs only; see PPOPlayer.act_packed)
                        packed = codec.encode(
                            next_obs,
                            extra={"rewards": pending["rewards"], "dones": pending["dones"]}
                            if pending
                            else zero_extra,
                        )
                        cat_actions, env_actions, _, values, player_rng = player.act_packed(
                            codec, packed, player_rng
                        )
                        # the one unavoidable per-step device->host sync: env actions
                        real_actions = np.asarray(env_actions)
                        stepper.step_async(real_actions.reshape(envs.action_space.shape))

                        # ---- overlap window: env workers are stepping
                        _process_pending(packed)
                        if device_rollout:
                            # in-graph scatter: actions/values stay in HBM (A2C's loss
                            # recomputes logprobs, so only these two leaves are stored)
                            rb.add_policy({"actions": cat_actions, "values": values})

                        obs, rewards, terminated, truncated, info = stepper.step_wait()
                        dones = np.logical_or(terminated, truncated).reshape(n_envs, -1).astype(np.uint8)
                        rewards = np.asarray(rewards, dtype=np.float32).reshape(n_envs, -1)

                        pending.update(
                            packed=packed,
                            rewards=rewards,
                            dones=dones,
                            info=info,
                            values=values,
                            cat_actions=cat_actions,
                        )

                        next_obs = {}
                        for k in obs_keys:
                            next_obs[k] = obs[k]

                with timer("Time/env_interaction_time", SumMetric()):
                    # flush: the rollout's last row has no next act transfer to ride
                    _process_pending(None)

            # ----- optimization phase: single jitted call. The fused path
            # already ran its update inside the one program above.
            if fused_trainer is None:
                if not device_rollout and not use_ingraph:
                    local_data = rb.to_arrays(dtype=np.float32)
                    if cfg.buffer.size > cfg.algo.rollout_steps:
                        idx = np.arange(rb._pos - cfg.algo.rollout_steps, rb._pos) % cfg.buffer.size
                        local_data = {k: v[idx] for k, v in local_data.items()}
                with trace.span("train/update", iter=iter_num), timer(
                    "Time/train_time", SumMetric()
                ):
                    if iter_num == start_iter:
                        # surface any residual warmup compile time here rather than
                        # inside the train call (the rollout overlapped the thread)
                        warmup.wait()
                    rng, train_key = jax.random.split(rng)
                    # ----- donated per-shard handoff (parallel/handoff.py): the
                    # [T, B, *] rollout shards on the env axis (B) so GAE's scan
                    # over T stays shard-local — each mesh device receives ONE
                    # put of only its env block instead of a full replicated
                    # copy. Bootstrap values are tiny and stay replicated.
                    if use_ingraph:
                        device_data = handoff.shard_put(
                            ingraph_data, runtime.mesh, batch_axis=1
                        )
                        next_values = runtime.replicate(ingraph_next_values)
                    elif device_rollout:
                        jax_obs = prepare_obs(runtime, next_obs, num_envs=n_envs)
                        device_data = handoff.shard_put(
                            rb.rollout(), runtime.mesh, batch_axis=1
                        )
                        next_values = runtime.replicate(player.get_values(jax_obs))
                    else:
                        jax_obs = prepare_obs(runtime, next_obs, num_envs=n_envs)
                        next_values = np.asarray(player.get_values(jax_obs))
                        device_data = handoff.shard_put(
                            {k: v for k, v in local_data.items() if k not in ("returns", "advantages")},
                            runtime.mesh,
                            batch_axis=1,
                        )
                    failpoints.failpoint(
                        "train.grad_sync", iter=iter_num, microbatches=overlap.microbatches(cfg)
                    )
                    params, opt_state, flat_params, train_metrics = train_fn(
                        params, opt_state, device_data, next_values, train_key,
                        jnp.float32(sentinel.lr_scale),
                    )
                    player.params = params_sync.pull(flat_params, player_sync_device)
                    if not timer.disabled:
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
                            # MFU from the compiler's own cost model (ppo.py
                            # scheme): per-call FLOPs captured off
                            # cost_analysis() when the fused/split train
                            # executable AOT-compiled
                            _train_gfn = fused_trainer.step_fn if fused_trainer is not None else train_fn
                            _mfu = tel_device.mfu(
                                getattr(_train_gfn, "last_step_flops", None),
                                timer_metrics["Time/train_time"] / max(train_step - last_train, 1),
                                runtime.device,
                            )
                            if _mfu is not None:
                                logger.log_metrics({"Time/mfu": _mfu}, policy_step)
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

            resilience.enforce_nonfinite_policy(ft, train_metrics)
            env_deltas = resilience.drain_env_counters(envs, aggregator)
            jax_compile.drain_compile_counters(aggregator)
            if iter_num == start_iter:
                # everything reachable has compiled once: later traces are drift
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
                            jnp.asarray(rb_state["player_rng"]), runtime.player_device
                        )
                    player.params = params_sync.pull(params_sync.ravel(params), player_sync_device)
                    if sentinel.reseed_envs:
                        pending.clear()
                        reset_obs = envs.reset(seed=cfg.seed + iter_num)[0]
                        next_obs = {}
                        for k in obs_keys:
                            next_obs[k] = reset_obs[k]
                            step_data[k] = reset_obs[k][np.newaxis]
                        # the fused sharded step expects its carry back in the
                        # mesh layout after any reset
                        if fused_trainer is not None:
                            fused_trainer.shard_carry()
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
        if use_ingraph:
            ingraph_envs.test(player, runtime, cfg, log_dir)
        else:
            test(player, runtime, cfg, log_dir)
    if logger:
        logger.finalize()
