"""PPO, coupled training (reference sheeprl/algos/ppo/ppo.py:30-442).

TPU-first structure:
- the rollout loop stays on host (gym stepping is host work); the policy forward is a
  small jitted call per step;
- the entire optimization phase — GAE + update_epochs x minibatches — is ONE jitted
  function per iteration (`lax.scan` over minibatches), instead of the reference's
  Python loop of per-minibatch backward passes;
- data parallelism: the minibatch is shard-constrained on the `data` mesh axis with
  params replicated, so XLA inserts the gradient all-reduce over ICI (the DDP
  equivalent, SURVEY §2.1). `buffer.share_data` is implicitly true: the global
  permutation spans all devices' rollouts.
"""

from __future__ import annotations

import os
import time
import warnings
from functools import partial
from typing import Any, Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.algos.ppo.agent import build_agent, evaluate_actions
from sheeprl_tpu.algos.ppo.loss import entropy_loss, policy_loss, value_loss
from sheeprl_tpu.algos.ppo.utils import normalize_obs, prepare_obs, test
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.core import compile as jax_compile
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
from sheeprl_tpu.utils.utils import (
    PlayerParamsSync,
    gae,
    normalize_tensor,
    polynomial_decay,
    save_configs,
)


def make_update_impl(
    agent,
    tx,
    cfg,
    runtime,
    n_data: int,
    obs_keys,
    cnn_keys,
    params_sync=None,
    *,
    axis_name=None,
    shards=1,
    constrain_data=True,
    batch_size=None,
):
    """Build the raw (unjitted) per-iteration optimization function.

    Signature: (params, opt_state, data, next_values, key, coefs) ->
    (params, opt_state, flat_params, metrics). ``data`` is the whole rollout
    ``[T, B, ...]``; ``flat_params`` is the raveled post-update param vector for the
    one-transfer player refresh (None if no ``params_sync`` given).

    Two flavors share the trace:
    - default (``axis_name=None``): the jitted split-path train step AND the
      single-device fused iteration's update phase (envs/ingraph/fused.py);
    - ``axis_name="data"``/``shards=N``: the body runs shard-local inside
      ``shard_map`` — permutations index the ``n_data/N`` local rows, minibatch
      grads (and the nonfinite guard's decision scalars, so every shard takes
      the identical apply-or-skip branch) all-reduce via ``jax.lax.pmean``.
      Per-shard minibatches of ``global_bs/N`` keep the effective global batch
      identical to the split path.
    """
    update_epochs = int(cfg.algo.update_epochs)
    # the default global batch assumes the mesh is DATA-parallel (every device
    # holds a slice of one rollout); the population trainer's mesh shards
    # MEMBERS instead — each member updates locally over its own n_data rows —
    # so it pins batch_size=per_rank_batch_size explicitly
    global_bs = (
        int(batch_size) if batch_size is not None
        else int(cfg.algo.per_rank_batch_size) * runtime.world_size
    )
    shards = int(shards)
    local_n = n_data // shards
    local_bs = max(global_bs // shards, 1)
    n_minibatches = max(local_n // local_bs, 1)
    # constrain_data=False drops the explicit data-axis sharding constraint:
    # the population trainer (envs/ingraph/population.py) vmaps this body over
    # a member axis (and may run it inside shard_map), where the constraint's
    # env-batch placement no longer applies.
    data_sharding = (
        NamedSharding(runtime.mesh, P("data")) if (axis_name is None and constrain_data) else None
    )
    nonfinite_guard = resilience.guard_enabled(resilience.resolve(cfg))

    def loss_fn(params, batch, clip_coef, ent_coef):
        norm_obs = normalize_obs(batch, cnn_keys, obs_keys)
        actions = jnp.split(
            batch["actions"], np.cumsum(agent.actions_dim)[:-1].tolist(), axis=-1
        ) if len(agent.actions_dim) > 1 else [batch["actions"]]
        actor_outs, new_values = agent.apply(params, norm_obs)
        new_logprobs, entropy = evaluate_actions(actor_outs, actions, agent.is_continuous, agent.distribution)
        advantages = batch["advantages"]
        if cfg.algo.normalize_advantages:
            advantages = normalize_tensor(advantages)
        pg_loss = policy_loss(new_logprobs, batch["logprobs"], advantages, clip_coef, cfg.algo.loss_reduction)
        v_loss = value_loss(
            new_values, batch["values"], batch["returns"], clip_coef, cfg.algo.clip_vloss, cfg.algo.loss_reduction
        )
        ent_loss = entropy_loss(entropy, cfg.algo.loss_reduction)
        total = pg_loss + cfg.algo.vf_coef * v_loss + ent_coef * ent_loss
        return total, (pg_loss, v_loss, ent_loss)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    micro = overlap.microbatches(cfg)

    def train(params, opt_state, data, next_values, key, clip_coef, ent_coef, lr_scale):
        # ----- GAE on device (reverse lax.scan over T; reference utils.py:64-100)
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
        # flatten [T, B, *] -> [N, *]
        flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in data.items()}

        if update_epochs == 1 and n_minibatches == 1 and local_bs >= local_n:
            # ONE minibatch covering every row: a permutation only reorders the
            # batch mean, so skip the O(N log N) sort and the full-data gather
            perms = None
        else:
            n_keep = n_minibatches * local_bs
            epoch_keys = jax.random.split(key, update_epochs)
            perms = jnp.stack([jax.random.permutation(k, local_n)[:n_keep] for k in epoch_keys])
            perms = perms.reshape(update_epochs * n_minibatches, local_bs)

        def minibatch_step(carry, idx):
            params, opt_state = carry
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
            # grad_microbatches=1 is the verbatim single-batch backward + one
            # pmean; >1 runs the bucketed accumulation scan with a per-bucket
            # psum (parallel/overlap.py) — grads come back already axis-averaged
            (loss, (pg, vl, ent)), grads = overlap.accumulate_grads(
                grad_fn, params, batch, (clip_coef, ent_coef),
                microbatches=micro, axis_name=axis_name, axis_size=shards,
            )
            if axis_name is not None:
                # the loss scalars reduce too so the finite_or_skip decision
                # below is replicated across shards (a shard-local skip would
                # silently fork the param replicas)
                loss, pg, vl, ent = (jax.lax.pmean(x, axis_name) for x in (loss, pg, vl, ent))
            gnorm = optax.global_norm(grads)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            # health-sentinel LR backoff: a traced scalar operand (no retrace on
            # change); the healthy value is exactly 1.0, and x * 1.0 is IEEE-
            # exact, so a disabled/quiet sentinel leaves updates bit-identical
            updates = jax.tree_util.tree_map(lambda u: u * lr_scale, updates)
            new_params = optax.apply_updates(params, updates)
            if nonfinite_guard:
                (params, opt_state), skipped = resilience.finite_or_skip(
                    (loss, gnorm), (new_params, new_opt_state), (params, opt_state)
                )
            else:
                params, opt_state, skipped = new_params, new_opt_state, jnp.float32(0.0)
            return (params, opt_state), jnp.stack([pg, vl, ent, skipped, gnorm])

        (params, opt_state), losses = jax.lax.scan(
            minibatch_step, (params, opt_state), perms, length=1 if perms is None else None
        )
        metrics = losses.mean(axis=0)
        flat = params_sync.ravel(params) if params_sync is not None else jnp.zeros(())
        return params, opt_state, flat, {
            "Loss/policy_loss": metrics[0],
            "Loss/value_loss": metrics[1],
            "Loss/entropy_loss": metrics[2],
            "Resilience/nonfinite_skips": losses[:, 3].sum(),
            "Grads/global_norm": metrics[4],
        }

    return train


def make_train_fn(
    agent, tx, cfg, runtime, n_data: int, obs_keys, cnn_keys, params_sync=None, *, donate_data=False
):
    """The jitted split-path train step (see :func:`make_update_impl`).

    ``donate_data=True`` additionally donates the rollout ``data`` tree — safe
    when every caller hands over a freshly assembled batch it never reads
    again (the decoupled trainer's per-shard handoff does exactly that; the
    coupled loop keeps the default so diagnostic spies can still read it)."""
    train = make_update_impl(agent, tx, cfg, runtime, n_data, obs_keys, cnn_keys, params_sync)
    donate = (0, 1, 2) if donate_data else (0, 1)
    return jax_compile.guarded_jit(train, name="ppo.train", donate_argnums=donate)


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    use_ingraph = ingraph_envs.env_backend(cfg) == "ingraph"
    if not use_ingraph and "minedojo" in cfg.env.wrapper._target_.lower():
        raise ValueError(
            "MineDojo is not currently supported by PPO agent, since it does not take "
            "into consideration the action masks provided by the environment, but needed "
            "in order to play correctly the game. "
            "As an alternative you can use one of the Dreamers' agents."
        )
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
    if runtime.is_global_zero and log_dir:
        # compiled-program observatory: every AOT compile below (act step,
        # fused trainer, split train fn) lands a ledger row here — unless a
        # parent pinned SHEEPRL_TPU_PROGRAMS, which wins (one ledger per tree)
        tel_programs.configure_default(os.path.join(log_dir, "telemetry", "programs.jsonl"))

    # Environment setup: one process drives world_size * num_envs envs (per-rank
    # semantics of the reference are per-device here).
    ft = resilience.resolve(cfg)
    sentinel = health_mod.HealthSentinel(
        cfg, log_dir=log_dir if runtime.is_global_zero else None, world_size=world_size
    )
    n_envs = cfg.env.num_envs * world_size
    if use_ingraph:
        # in-graph backend: no worker pool, no supervision layer — the whole
        # batch of envs is one device-resident pytree stepped inside the fused
        # rollout (envs/ingraph/). Collection runs on the accelerator even when
        # the player would normally sit on host.
        collect_device = runtime.device
        envs = ingraph_envs.make_vector_env(cfg, n_envs, cfg.seed, device=collect_device)
    else:
        envs = resilience.make_supervised_env(
            [
                make_env(
                    cfg,
                    cfg.seed + i,
                    0,
                    log_dir if runtime.is_global_zero else None,
                    "train",
                    vector_env_idx=i,
                )
                for i in range(n_envs)
            ],
            sync=cfg.env.sync_env,
            ft=ft,
        )
    observation_space = envs.single_observation_space
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder == []:
        raise RuntimeError(
            "You should specify at least one CNN keys or MLP keys from the cli: "
            "`cnn_keys.encoder=[rgb]` or `mlp_keys.encoder=[state]`"
        )
    if cfg.metric.log_level > 0:
        runtime.print("Encoder CNN keys:", cfg.algo.cnn_keys.encoder)
        runtime.print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
    obs_keys = cfg.algo.cnn_keys.encoder + cfg.algo.mlp_keys.encoder
    cnn_keys = cfg.algo.cnn_keys.encoder

    is_continuous = isinstance(envs.single_action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(envs.single_action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        envs.single_action_space.shape
        if is_continuous
        else (envs.single_action_space.nvec.tolist() if is_multidiscrete else [envs.single_action_space.n])
    )
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)

    agent, params, player = build_agent(
        runtime,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["agent"] if state else None,
    )
    if use_ingraph:
        # policy forward happens inside the scan on the collect device, not on
        # the (host) player device build_agent placed the params on
        player.params = jax.device_put(player.params, collect_device)
    player_sync_device = collect_device if use_ingraph else runtime.player_device

    # Optimizer: optax chain (clipping + optional linear lr decay = PolynomialLR(power=1))
    policy_steps_per_iter = int(n_envs * cfg.algo.rollout_steps)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    n_data = cfg.algo.rollout_steps * n_envs
    global_bs = int(cfg.algo.per_rank_batch_size) * world_size
    updates_per_iter = int(cfg.algo.update_epochs) * max(n_data // global_bs, 1)
    optim_kwargs = dict(cfg.algo.optimizer)
    if cfg.algo.anneal_lr:
        lr0 = optim_kwargs.pop("lr", 1e-3)
        optim_kwargs["lr"] = optax.linear_schedule(lr0, 0.0, total_iters * updates_per_iter)
    tx = with_clipping(instantiate(optim_kwargs)(), cfg.algo.max_grad_norm)
    opt_state = tx.init(params)
    if state:
        opt_state = jax.tree_util.tree_map(jnp.asarray, state["optimizer"])
    opt_state = runtime.place_params(opt_state)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(cfg.metric.aggregator)

    if cfg.buffer.size < cfg.algo.rollout_steps:
        raise ValueError(
            f"The size of the buffer ({cfg.buffer.size}) cannot be lower "
            f"than the rollout steps ({cfg.algo.rollout_steps})"
        )
    rb = make_rollout_buffer(cfg, runtime, n_envs, obs_keys, log_dir)
    # device backend: the [T, B] rollout lives in HBM; policy outputs never
    # touch host and the per-step host->device traffic is one packed put
    device_rollout = getattr(rb, "backend", "host") == "device"

    # Counters (same step semantics as the reference, howto/work_with_steps.md)
    last_train = 0
    train_step = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state else 1
    policy_step = state["iter_num"] * cfg.env.num_envs * cfg.algo.rollout_steps if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    if state:
        cfg.algo.per_rank_batch_size = state["batch_size"] // world_size

    if cfg.metric.log_level > 0 and cfg.metric.log_every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The metric.log_every parameter ({cfg.metric.log_every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter}), so "
            "the metrics will be logged at the nearest greater multiple of the policy_steps_per_iter value."
        )
    if cfg.checkpoint.every % policy_steps_per_iter != 0:
        warnings.warn(
            f"The checkpoint.every parameter ({cfg.checkpoint.every}) is not a multiple of the "
            f"policy_steps_per_iter value ({policy_steps_per_iter}), so "
            "the checkpoint will be saved at the nearest greater multiple of the policy_steps_per_iter value."
        )

    params_sync = PlayerParamsSync(player.params)
    train_fn = make_train_fn(agent, tx, cfg, runtime, n_data, obs_keys, cnn_keys, params_sync)
    profiler = TraceProfiler(cfg.metric.get("profiler"), log_dir if runtime.is_global_zero else None)
    rng = jax.random.PRNGKey(cfg.seed)
    # Separate rollout key committed to the player device: the policy forward then
    # runs entirely there (mixing committed arrays across backends is an error).
    player_rng = jax.device_put(jax.random.PRNGKey(cfg.seed + 1), runtime.player_device)
    if state and "rng" in state:
        # restore the EXACT key chains so a preempted run resumes bit-identically
        # to the uninterrupted one (older checkpoints lack these: seed restart)
        rng = jnp.asarray(state["rng"])
        player_rng = jax.device_put(jnp.asarray(state["player_rng"]), runtime.player_device)

    step_data = {}
    reset_obs = envs.reset(seed=cfg.seed)[0]
    next_obs = {}
    for k in obs_keys:
        _obs = reset_obs[k]
        if k in cnn_keys:
            _obs = _obs.reshape(n_envs, -1, *_obs.shape[-2:])
        next_obs[k] = _obs
        step_data[k] = _obs[np.newaxis]

    # ----- software pipeline (core/pipeline.py): the env workers step while the
    # host closes out the PREVIOUS step and dispatches this one's device work;
    # the obs reach the device as ONE packed put per step with the previous
    # step's rewards/dones riding along for the buffer's row-close write
    stepper = AsyncEnvStepper(envs, enabled=pipeline_enabled(cfg) and not use_ingraph)
    codec = PackedObsCodec(cnn_keys=cnn_keys, device=runtime.player_device)
    collector = None
    fused_trainer = None
    if use_ingraph:
        collector = ingraph_envs.InGraphRolloutCollector(
            envs,
            player,
            rollout_steps=cfg.algo.rollout_steps,
            gamma=cfg.algo.gamma,
            clip_rewards=cfg.env.clip_rewards,
            store_logprobs=True,
            name="ppo",
        )
        if ingraph_envs.fused_enabled(cfg):
            # ----- whole-iteration fusion (envs/ingraph/fused.py): rollout scan
            # + GAE + all update epochs compile into ONE program per iteration;
            # on a multi-device mesh the env batch shards on the `data` axis and
            # gradients all-reduce in-graph (pmean inside the update impl)
            update_impl = make_update_impl(
                agent,
                tx,
                cfg,
                runtime,
                n_data,
                obs_keys,
                cnn_keys,
                params_sync,
                axis_name="data" if world_size > 1 else None,
                shards=world_size,
            )
            fused_trainer = ingraph_envs.FusedInGraphTrainer(
                collector,
                update_impl,
                n_extras=3,
                mesh=runtime.mesh if world_size > 1 else None,
                name="ppo",
            )
            fused_trainer.shard_carry()
    zero_extra = {
        "rewards": np.zeros((n_envs, 1), np.float32),
        "dones": np.zeros((n_envs, 1), np.float32),
    }

    # ----- AOT warmup (core/compile.py): compile the packed-act step, the fused
    # train step, and the metric-drain kernels on a background thread while the
    # first rollout collects; the first train call then executes a pre-built
    # executable (trace count 0 at call time, Compile/retraces stays 0).
    warmup = jax_compile.AOTWarmup(enabled=jax_compile.aot_enabled(cfg))
    if warmup.enabled and use_ingraph:
        if fused_trainer is not None:
            # ONE entry point for the whole iteration: collect + GAE + update
            # epochs. The specs come from the live (mesh-sharded, for the
            # shard_map variant) params/opt_state/carry, so the background
            # compile targets the exact steady-state placements.
            warmup.add(
                fused_trainer.step_fn,
                *fused_trainer.warmup_specs(
                    params,
                    opt_state,
                    rng,
                    jnp.float32(cfg.algo.clip_coef),
                    jnp.float32(cfg.algo.ent_coef),
                    jnp.float32(1.0),
                ),
            )
        else:
            # the whole rollout is ONE entry point (the fused scan); its abstract
            # outputs are exactly the train step's inputs, so both specs derive
            # without touching the device
            warmup.add(collector.collect_fn, *collector.warmup_specs())
            data_specs, nv_spec = collector.output_specs()
            warmup.add(
                train_fn,
                jax_compile.specs_of(params),
                jax_compile.specs_of(opt_state),
                # the handoff below assembles the batch PRE-SHARDED on the mesh
                # (env axis): the warmup specs must carry that layout or the
                # AOT executable rejects the real batch at call time
                handoff.shard_specs(data_specs, runtime.mesh, batch_axis=1),
                jax.ShapeDtypeStruct(nv_spec.shape, jnp.float32, sharding=runtime.replicated),
                jax_compile.spec_like(rng),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32),
            )
        if aggregator is not None:
            warmup.add_task(
                lambda: aggregator.precompile_drain(
                    (
                        "Loss/policy_loss",
                        "Loss/value_loss",
                        "Loss/entropy_loss",
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
            # train-step specs from the resolved config + the act step's
            # abstract outputs (jax.eval_shape: no FLOPs, no transfers); the
            # device-backend rollout keeps JIT-on-first-call (its storage
            # layout is the buffer's concern, not derivable here)
            cat_s, _env_s, logp_s, val_s, _key_s = jax.eval_shape(act_fn.fun, *act_specs)
            T = int(cfg.algo.rollout_steps)
            data_specs = {
                k: jax.ShapeDtypeStruct((T, *next_obs[k].shape), jnp.float32) for k in obs_keys
            }
            for k, s in (("actions", cat_s), ("logprobs", logp_s), ("values", val_s)):
                data_specs[k] = jax.ShapeDtypeStruct((T, *s.shape), jnp.float32)
            for k in ("rewards", "dones"):
                data_specs[k] = jax.ShapeDtypeStruct((T, n_envs, 1), jnp.float32)
            warmup.add(
                train_fn,
                jax_compile.specs_of(params),
                jax_compile.specs_of(opt_state),
                # the host rollout enters the mesh shard-at-put (env axis) —
                # warmup against that layout, not a replicated one
                handoff.shard_specs(data_specs, runtime.mesh, batch_axis=1),
                jax.ShapeDtypeStruct(val_s.shape, jnp.float32),
                jax_compile.spec_like(rng),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32),
            )
        if aggregator is not None:
            warmup.add_task(
                lambda: aggregator.precompile_drain(
                    (
                        "Loss/policy_loss",
                        "Loss/value_loss",
                        "Loss/entropy_loss",
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
        """Close out the previous step while the env workers run: buffer row
        write, episode/metric accounting. ``cur_packed`` is the current step's
        packed transfer carrying the pending rewards/dones (None at the
        end-of-rollout flush, where a short extra-only put stands in)."""
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
            # obs decode from the PREVIOUS step's act transfer, rewards/dones
            # from the current one: closing a row costs zero extra transfers
            rb.add_env_packed(codec, pending["packed"], extra_packed, extra_only=extra_only)
        else:
            rewards = pending["rewards"]
            step_data["dones"] = pending["dones"][np.newaxis]
            step_data["values"] = np.asarray(pending["values"])[np.newaxis]
            step_data["actions"] = np.asarray(pending["cat_actions"])[np.newaxis]
            step_data["logprobs"] = np.asarray(pending["logprobs"])[np.newaxis]
            step_data["rewards"] = rewards[np.newaxis]
            if cfg.buffer.memmap:
                step_data["returns"] = np.zeros_like(rewards, shape=(1, *rewards.shape))
                step_data["advantages"] = np.zeros_like(rewards, shape=(1, *rewards.shape))
            rb.add(step_data, validate_args=cfg.buffer.validate_args)
            # the row just written holds the obs the pending step acted on; the
            # NEXT row starts from the obs that step produced (current next_obs)
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

        The pull is the ONLY bulk host traffic an ingraph iteration performs, so
        it is skipped outright when nothing consumes it: aggregator disabled, or
        between ``log_every`` drains (finished episodes are then sampled at the
        drain iterations rather than fetched every iteration)."""
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
                # rollout scan, GAE, and every update epoch run as ONE compiled
                # donated-carry program; only the raveled params and metric
                # leaves return to the host. Chaos seam first, so drills and
                # the sentinel's rollback ladder cover the fused path too.
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
                        fused_trainer.to_mesh(jnp.float32(cfg.algo.clip_coef)),
                        fused_trainer.to_mesh(jnp.float32(cfg.algo.ent_coef)),
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
                # scan (envs/ingraph/rollout.py) followed by the separately
                # jitted train step below — the fused path's parity reference
                with trace.span("train/collect", iter=iter_num), timer(
                    "Time/env_interaction_time", SumMetric()
                ):
                    policy_step += n_envs * cfg.algo.rollout_steps
                    ingraph_data, roll_metrics, ingraph_next_values = collector.collect()
                # zero-cost unless an env.autoreset drill is armed (the has()
                # probe short-circuits before any device pull)
                envs.fire_autoreset_failpoints(roll_metrics["dones"])
                _drain_ingraph_episodes(roll_metrics)
            else:
                _collect_t0 = time.perf_counter()
                for _ in range(cfg.algo.rollout_steps):
                    policy_step += n_envs

                    with timer("Time/env_interaction_time", SumMetric()):
                        # ONE packed host->device transfer per step: obs plus the
                        # previous step's rewards/dones (decoded only by the buffer
                        # write), normalization runs in-graph (PPOPlayer.act_packed)
                        packed = codec.encode(
                            next_obs,
                            extra={"rewards": pending["rewards"], "dones": pending["dones"]}
                            if pending
                            else zero_extra,
                        )
                        cat_actions, env_actions, logprobs, values, player_rng = player.act_packed(
                            codec, packed, player_rng
                        )
                        # the ONE unavoidable per-step device->host sync: the env needs
                        # the actions on host to step
                        real_actions = np.asarray(env_actions)
                        stepper.step_async(real_actions.reshape(envs.action_space.shape))

                        # ---- overlap window: env workers are stepping; close out the
                        # previous step and dispatch this one's policy-row scatter
                        _process_pending(packed)
                        if device_rollout:
                            # in-graph scatter straight from the player step's outputs:
                            # values/logprobs/actions stay in HBM, no host pull
                            rb.add_policy({"actions": cat_actions, "logprobs": logprobs, "values": values})

                        obs, rewards, terminated, truncated, info = stepper.step_wait()
                        truncated_envs = np.nonzero(truncated)[0]
                        if len(truncated_envs) > 0 and "final_obs" in info:
                            # bootstrap on truncation (reference ppo.py:292-309)
                            final_obs_arr = np.asarray(info["final_obs"], dtype=object)
                            real_next_obs = {k: [] for k in obs_keys}
                            valid_idx = []
                            for te in truncated_envs:
                                fo = final_obs_arr[te]
                                if fo is None:
                                    continue
                                valid_idx.append(te)
                                for k in obs_keys:
                                    v = np.asarray(fo[k], dtype=np.float32)
                                    if k in cnn_keys:
                                        v = v.reshape(-1, *v.shape[-2:]) / 255.0 - 0.5
                                    real_next_obs[k].append(v)
                            if valid_idx:
                                # canonical shape: pad to the FULL [n_envs, ...] batch and
                                # gather the valid rows after, so the values forward keeps
                                # ONE compiled shape no matter how many envs truncated
                                # (1..n_envs distinct shapes would otherwise each compile)
                                padded = {
                                    k: np.zeros((n_envs, *np.asarray(v[0]).shape), np.float32)
                                    for k, v in real_next_obs.items()
                                }
                                for j, te in enumerate(valid_idx):
                                    for k in obs_keys:
                                        padded[k][te] = real_next_obs[k][j]
                                stacked = {
                                    k: jax.device_put(v, runtime.player_device) for k, v in padded.items()
                                }
                                vals = np.asarray(player.get_values(stacked)).reshape(n_envs)
                                rewards = np.asarray(rewards, dtype=np.float32)
                                rewards[valid_idx] += cfg.algo.gamma * vals[valid_idx]
                        dones = np.logical_or(terminated, truncated).reshape(n_envs, -1).astype(np.uint8)
                        rewards = clip_rewards_fn(np.asarray(rewards, dtype=np.float32)).reshape(n_envs, -1)

                        # env products become the next step's pending work: the row
                        # write and episode accounting run in the NEXT overlap window
                        pending.update(
                            packed=packed,
                            rewards=rewards,
                            dones=dones,
                            info=info,
                            values=values,
                            cat_actions=cat_actions,
                            logprobs=logprobs,
                        )

                        next_obs = {}
                        for k in obs_keys:
                            _obs = obs[k]
                            if k in cnn_keys:
                                _obs = _obs.reshape(n_envs, -1, *_obs.shape[-2:])
                            next_obs[k] = _obs

                with timer("Time/env_interaction_time", SumMetric()):
                    # flush: the rollout's last row has no next act transfer to ride
                    _process_pending(None)
                # whole host-rollout phase as one span (explicit timestamps: the
                # per-step loop is too hot to wrap per step)
                trace.add_span(
                    "train/collect", _collect_t0, time.perf_counter(), clock="perf", iter=iter_num
                )

            # ----- optimization phase: single jitted call (GAE + epochs x minibatches).
            # The fused path already ran its update inside the one program above.
            if fused_trainer is None:
                if not device_rollout and not use_ingraph:
                    local_data = rb.to_arrays(dtype=np.float32)
                    if cfg.buffer.size > cfg.algo.rollout_steps:
                        # keep only the last rollout in chronological order (stale/zero rows
                        # beyond the write head would corrupt GAE)
                        idx = np.arange(rb._pos - cfg.algo.rollout_steps, rb._pos) % cfg.buffer.size
                        local_data = {k: v[idx] for k, v in local_data.items()}
                with trace.span("train/update", iter=iter_num), timer(
                    "Time/train_time", SumMetric()
                ):
                    if iter_num == start_iter:
                        # every registered entry point compiled before the first
                        # train dispatch (usually already done: the whole first
                        # rollout overlapped the warmup thread)
                        warmup.wait()
                    rng, train_key = jax.random.split(rng)
                    # ----- per-shard rollout handoff (parallel/handoff.py): the
                    # bulk [T, B, *] rollout is assembled mesh-sharded on the env
                    # axis — one put per device shard, no full-batch replication,
                    # no post-put host-side copy; only the small bootstrap values
                    # still replicate. GAE then runs shard-local over B.
                    if use_ingraph:
                        device_data = handoff.shard_put(ingraph_data, runtime.mesh, batch_axis=1)
                        next_values = runtime.replicate(ingraph_next_values)
                    elif device_rollout:
                        # the completed HBM rollout and the bootstrap values move
                        # player-device -> trainer-mesh directly (ownership
                        # transfers out of the buffer, so the train fn's view is
                        # never aliased by next iteration's donated writes)
                        jax_obs = prepare_obs(runtime, next_obs, cnn_keys=cnn_keys, num_envs=n_envs)
                        device_data = handoff.shard_put(rb.rollout(), runtime.mesh, batch_axis=1)
                        next_values = runtime.replicate(player.get_values(jax_obs))
                    else:
                        # bootstrap values come from the player device; the host
                        # rollout enters the mesh shard-at-put
                        jax_obs = prepare_obs(runtime, next_obs, cnn_keys=cnn_keys, num_envs=n_envs)
                        next_values = np.asarray(player.get_values(jax_obs))
                        device_data = handoff.shard_put(
                            {k: v for k, v in local_data.items() if k not in ("returns", "advantages")},
                            runtime.mesh,
                            batch_axis=1,
                        )
                    # chaos seam for the (possibly microbatched) gradient-sync
                    # dispatch — the split-path twin of train.fused_update above
                    failpoints.failpoint(
                        "train.grad_sync", iter=iter_num, microbatches=overlap.microbatches(cfg)
                    )
                    params, opt_state, flat_params, train_metrics = train_fn(
                        params,
                        opt_state,
                        device_data,
                        next_values,
                        train_key,
                        jnp.float32(cfg.algo.clip_coef),
                        jnp.float32(cfg.algo.ent_coef),
                        jnp.float32(sentinel.lr_scale),
                    )
                    # refresh the player's copy with ONE cross-backend transfer; the next
                    # rollout implicitly waits for (only) the params it needs
                    player.params = params_sync.pull(flat_params, player_sync_device)
                    if not timer.disabled:  # sync only when the train phase is being timed
                        jax.block_until_ready(params)
                train_step += world_size

            if cfg.metric.log_level > 0:
                if aggregator:
                    aggregator.update_from_device(train_metrics)
                logger.log_metrics({"Info/clip_coef": cfg.algo.clip_coef, "Info/ent_coef": cfg.algo.ent_coef}, policy_step)
                if policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters:
                    _drain_t0 = time.perf_counter()
                    overlap_s, overlap_steps = stepper.drain_overlap()
                    if overlap_s > 0:
                        # env-step throughput absorbed into the overlap window
                        # (env time hidden behind device dispatch + host bookkeeping)
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
                            # MFU from the compiler's own cost model: the train
                            # fn's per-call FLOPs were captured off
                            # cost_analysis() when its executable AOT-compiled
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
                    trace.add_span(
                        "train/metric_drain",
                        _drain_t0,
                        time.perf_counter(),
                        clock="perf",
                        step=policy_step,
                    )
                    last_log = policy_step
                    last_train = train_step

            # Anneal coefficients (lr annealing lives in the optax schedule)
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
                # steady-state watermark: everything this loop will ever compile
                # has compiled; any retrace from here is a perf cliff
                jax_compile.mark_steady()

            # ----- health sentinel (core/health.py): one check per iteration over
            # the metrics this loop already produced; detections climb the
            # warn -> backoff (lr_scale operand above) -> rollback ladder
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
                        # drop the in-flight transition (it was produced by the
                        # poisoned policy) and restart the streams on a fresh seed
                        pending.clear()
                        reset_obs = envs.reset(seed=cfg.seed + iter_num)[0]
                        next_obs = {}
                        for k in obs_keys:
                            _obs = reset_obs[k]
                            if k in cnn_keys:
                                _obs = _obs.reshape(n_envs, -1, *_obs.shape[-2:])
                            next_obs[k] = _obs
                            step_data[k] = _obs[np.newaxis]
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
                with trace.span("train/checkpoint", step=policy_step):
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
                    ckpt_path = os.path.join(
                        log_dir, f"checkpoint/ckpt_{policy_step}_{runtime.global_rank}.ckpt"
                    )
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
    if trace.enabled() and runtime.is_global_zero and log_dir:
        try:
            trace.export(os.path.join(log_dir, "telemetry", "trace.json"))
        except OSError:
            pass
    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test:
        if use_ingraph:
            ingraph_envs.test(player, runtime, cfg, log_dir)
        else:
            test(player, runtime, cfg, log_dir)
    if logger:
        logger.finalize()
