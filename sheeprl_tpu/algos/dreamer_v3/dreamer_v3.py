"""DreamerV3, coupled training (reference sheeprl/algos/dreamer_v3/dreamer_v3.py:48-393).

TPU-first train step: per iteration the buffer is sampled once for all G gradient
steps ([G, T, B, *] batch) and ONE jitted call `lax.scan`s over G. Each gradient step
fuses (a) the world-model update — encoder forward batched over [T,B], RSSM dynamic
unrolled by `lax.scan` over T (the reference loops in Python, dreamer_v3.py:138-151) —
(b) the actor update with the H-step imagination `lax.scan` differentiated end-to-end,
and (c) the two-hot critic update with an in-graph conditional target-critic EMA.
The batch axis is sharded over the `data` mesh axis; XLA inserts the gradient
all-reduce over ICI (replacing Fabric DDP), and the Moments quantile runs on the
global batch (replacing the reference's fabric.all_gather, utils.py:57).
"""

from __future__ import annotations

import os
import time
import warnings
from functools import partial
from typing import Any, Dict, NamedTuple, Sequence

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.algos.dreamer_v3.agent import ActorOutput, DV3Modules, build_agent
from sheeprl_tpu.algos.dreamer_v3.loss import reconstruction_loss
from sheeprl_tpu.algos.dreamer_v3.utils import (
    MomentsState,
    compute_lambda_values,
    init_moments,
    test,
    update_moments,
)
from sheeprl_tpu.config import instantiate
from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.core import health as health_mod
from sheeprl_tpu.core import resilience
from sheeprl_tpu.core.pipeline import AsyncEnvStepper, PackedObsCodec, pipeline_enabled
from sheeprl_tpu.data.factory import make_sequential_replay
from sheeprl_tpu.envs.wrappers import RestartOnException
from sheeprl_tpu.telemetry import trace
from sheeprl_tpu.ops.distributions import (
    BernoulliSafeMode,
    Independent,
    MSEDistribution,
    OneHotCategorical,
    SymlogDistribution,
    TwoHotEncodingDistribution,
)
from sheeprl_tpu.utils.env import finished_episodes, final_observations, make_env, vectorized_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger
from sheeprl_tpu.utils.metric import MetricAggregator, SumMetric
from sheeprl_tpu.utils.optim import with_clipping
from sheeprl_tpu.utils.profiler import TraceProfiler
from sheeprl_tpu.utils.registry import register_algorithm
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import (
    NUMPY_TO_JAX_DTYPE,
    DreamerPlayerSync,
    Ratio,
    polyak_update,
    save_configs,
)

# Obs->latent->action world-model subset the rollout player needs (see
# PlayerDV3._raw_step / RSSM.initial_states); shipped to the player device by
# DreamerPlayerSync instead of the full world model.
PLAYER_WM_KEYS = (
    "encoder",
    "recurrent_model",
    "representation_model",
    "transition_model",
    "initial_recurrent_state",
)


# ``jax.named_scope`` names inside ``dv3.train``: metadata only (the program's
# outputs do not change), they name every device event of a profiler capture by
# the model part it belongs to. The first ten are the parts that
# ``benchmarks/chip/flops.py::dv3_step_flops`` counts, under its names, so a
# part's FLOPs divide by the part's device time with no mapping table; the rest
# is work it does not count.
TRAIN_SCOPES = (
    "encoder",
    "dynamic_scan",
    "decoder",
    "reward_head",
    "continue_head",
    "imagination_rollout",
    "imagination_actor",
    "imagination_heads",
    "critic_update",
    "target_critic",
    "world_opt",
    "actor_opt",
    "critic_opt",
    "moments",
    "target_ema",
    "player_ravel",
)
_scope = jax.named_scope


class DV3OptStates(NamedTuple):
    world: Any
    actor: Any
    critic: Any


@jax_compile.setup_phase("make_train_fn")
def make_train_fn(modules: DV3Modules, cfg, runtime, is_continuous: bool, actions_dim: Sequence[int], psync=None):
    """Build (init_opt, train) where train is a single jitted scan over G gradient steps."""
    if int(cfg.algo.get("grad_microbatches", 1) or 1) > 1:
        # DV3's world-model/actor/critic updates chain through the latent
        # rollout — chunking the [B, T] batch would change the sequence model's
        # statistics, not just the reduction order
        warnings.warn(
            "algo.grad_microbatches > 1 is not supported by DreamerV3; falling back to 1"
        )
    rssm = modules.rssm
    horizon = int(cfg.algo.horizon)
    gamma = float(cfg.algo.gamma)
    lmbda = float(cfg.algo.lmbda)
    ent_coef = float(cfg.algo.actor.ent_coef)
    kl_dynamic = float(cfg.algo.world_model.kl_dynamic)
    kl_representation = float(cfg.algo.world_model.kl_representation)
    kl_free_nats = float(cfg.algo.world_model.kl_free_nats)
    kl_regularizer = float(cfg.algo.world_model.kl_regularizer)
    continue_scale_factor = float(cfg.algo.world_model.continue_scale_factor)
    stoch_size = rssm.stoch_state_size
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cnn_keys_dec = list(cfg.algo.cnn_keys.decoder)
    mlp_keys_dec = list(cfg.algo.mlp_keys.decoder)
    target_freq = int(cfg.algo.critic.per_rank_target_network_update_freq)
    tau = float(cfg.algo.critic.tau)
    moments_cfg = cfg.algo.actor.moments
    actor_objective = str(cfg.algo.actor.get("objective", "auto"))
    if actor_objective not in ("auto", "reinforce"):
        raise ValueError(
            f"algo.actor.objective must be 'auto' or 'reinforce', got {actor_objective!r}"
        )
    imagination_unroll = int(cfg.algo.get("imagination_scan_unroll", 1))
    data_sharding = NamedSharding(runtime.mesh, P(None, "data"))
    nonfinite_guard = resilience.guard_enabled(resilience.resolve(cfg))

    world_tx = with_clipping(
        instantiate(dict(cfg.algo.world_model.optimizer))(), cfg.algo.world_model.clip_gradients
    )
    actor_tx = with_clipping(instantiate(dict(cfg.algo.actor.optimizer))(), cfg.algo.actor.clip_gradients)
    critic_tx = with_clipping(instantiate(dict(cfg.algo.critic.optimizer))(), cfg.algo.critic.clip_gradients)

    def init_opt(params) -> DV3OptStates:
        return DV3OptStates(
            world=world_tx.init(params["world_model"]),
            actor=actor_tx.init(params["actor"]),
            critic=critic_tx.init(params["critic"]),
        )

    def one_step(carry, inp):
        params, opt_states, moments_state, counter = carry
        data, key = inp
        data = jax.tree_util.tree_map(lambda v: jax.lax.with_sharding_constraint(v, data_sharding), data)
        k_wm, k_img0, k_img, k_actor = jax.random.split(key, 4)

        # ---- target critic EMA (reference dreamer_v3.py:740-753): tau=1 on first step
        def do_ema(tc):
            tau_eff = jnp.where(counter == 0, 1.0, tau)
            return jax.tree_util.tree_map(
                lambda p, tp: tau_eff * p + (1.0 - tau_eff) * tp, params["critic"], tc
            )

        with _scope("target_ema"):
            target_critic = jax.lax.cond(
                counter % target_freq == 0, do_ema, lambda tc: tc, params["target_critic"]
            )

        # ---- batch prep (in-graph: uint8 pixels stay uint8 until HBM)
        # batch_obs stays f32: these are the reconstruction-loss TARGETS (an f32
        # island of the precision audit). The encoder gets a compute-dtype view
        # below — its first layer casts anyway, so the values reaching the first
        # matmul are bitwise identical, but casting at the batch boundary stops
        # XLA from materializing the [T,B,C,H,W] normalization in f32 under
        # bf16-mixed (pure HBM-traffic win, audited in howto/performance.md).
        batch_obs = {k: data[k].astype(jnp.float32) / 255.0 - 0.5 for k in cnn_keys}
        batch_obs.update({k: data[k].astype(jnp.float32) for k in mlp_keys})
        encoder_obs = {k: v.astype(runtime.compute_dtype) for k, v in batch_obs.items()}
        is_first = data["is_first"].astype(jnp.float32).at[0].set(1.0)
        actions = data["actions"].astype(jnp.float32)
        batch_actions = jnp.concatenate([jnp.zeros_like(actions[:1]), actions[:-1]], axis=0)
        rewards = data["rewards"].astype(jnp.float32)
        continues_targets = 1.0 - data["terminated"].astype(jnp.float32)

        # ---- world-model update (Eq. 4)
        def world_loss_fn(wm_params):
            with _scope("encoder"):
                embedded = modules.encoder.apply(wm_params["encoder"], encoder_obs)
            with _scope("dynamic_scan"):
                recurrent_states, posteriors, priors_logits, posteriors_logits = rssm.dynamic_scan(
                    wm_params, embedded, batch_actions, is_first, k_wm
                )
            latent_states = jnp.concatenate(
                [posteriors.reshape(*posteriors.shape[:-2], -1), recurrent_states], axis=-1
            )
            with _scope("decoder"):
                reconstructed = modules.observation_model.apply(wm_params["observation_model"], latent_states)
                po_log_probs = {
                    k: MSEDistribution(reconstructed[k], dims=reconstructed[k].ndim - 2).log_prob(batch_obs[k])
                    for k in cnn_keys_dec
                }
                po_log_probs.update(
                    {
                        k: SymlogDistribution(reconstructed[k], dims=reconstructed[k].ndim - 2).log_prob(batch_obs[k])
                        for k in mlp_keys_dec
                    }
                )
            with _scope("reward_head"):
                pr = TwoHotEncodingDistribution(
                    modules.reward_model.apply(wm_params["reward_model"], latent_states), dims=1
                )
            with _scope("continue_head"):
                pc = Independent(
                    BernoulliSafeMode(
                        logits=modules.continue_model.apply(wm_params["continue_model"], latent_states)
                    ),
                    1,
                )
            loss, kl, state_loss, reward_loss, observation_loss, continue_loss = reconstruction_loss(
                po_log_probs,
                pr.log_prob(rewards),
                priors_logits,
                posteriors_logits,
                kl_dynamic,
                kl_representation,
                kl_free_nats,
                kl_regularizer,
                pc.log_prob(continues_targets),
                continue_scale_factor,
            )
            aux = {
                "posteriors": posteriors,
                "recurrent_states": recurrent_states,
                "priors_logits": priors_logits,
                "posteriors_logits": posteriors_logits,
                "kl": kl,
                "state_loss": state_loss,
                "reward_loss": reward_loss,
                "observation_loss": observation_loss,
                "continue_loss": continue_loss,
            }
            return loss, aux

        (world_loss, aux), world_grads = jax.value_and_grad(world_loss_fn, has_aux=True)(params["world_model"])
        with _scope("world_opt"):
            world_grad_norm = optax_global_norm(world_grads)
            world_updates, world_opt = world_tx.update(world_grads, opt_states.world, params["world_model"])
            new_wm = apply_updates(params["world_model"], world_updates)
            if nonfinite_guard:
                # a skipped world update also feeds the OLD world model to imagination below
                (new_wm, world_opt), wm_skipped = resilience.finite_or_skip(
                    (world_loss, world_grad_norm), (new_wm, world_opt), (params["world_model"], opt_states.world)
                )
            else:
                wm_skipped = jnp.float32(0.0)

        # ---- behaviour learning: imagination with the freshly-updated world model
        posteriors = jax.lax.stop_gradient(aux["posteriors"])  # [T, B, S, D]
        recurrent_states = jax.lax.stop_gradient(aux["recurrent_states"])  # [T, B, R]
        start_prior = posteriors.reshape(1, -1, stoch_size)[0]  # [T*B, S*D]
        start_recurrent = recurrent_states.reshape(1, -1, recurrent_states.shape[-1])[0]
        true_continue = continues_targets.reshape(-1, 1)  # [T*B, 1]

        def imagine(actor_params, key0, keys):
            """H+1-step differentiable imagination -> (trajectories, clipped actions,
            raw pre-clip samples — the score-function evaluation points)."""
            latent0 = jnp.concatenate([start_prior, start_recurrent], axis=-1)
            with _scope("imagination_actor"):
                out0 = ActorOutput(
                    modules.actor, modules.actor.apply(actor_params, jax.lax.stop_gradient(latent0))
                )
                acts0, raws0 = out0.sample_actions_with_raw(key0)
            actions0 = jnp.concatenate(acts0, axis=-1)
            raw0 = jnp.concatenate(raws0, axis=-1)

            def step(carry, k):
                prior_flat, rec_state, act = carry
                k_img_step, k_act_step = jax.random.split(k)
                with _scope("imagination_rollout"):
                    prior, rec_state = rssm.imagination_step(new_wm, prior_flat, rec_state, act, k_img_step)
                prior_flat = prior.reshape(prior_flat.shape)
                latent = jnp.concatenate([prior_flat, rec_state], axis=-1)
                with _scope("imagination_actor"):
                    out = ActorOutput(
                        modules.actor, modules.actor.apply(actor_params, jax.lax.stop_gradient(latent))
                    )
                    new_acts, new_raws = out.sample_actions_with_raw(k_act_step)
                new_act = jnp.concatenate(new_acts, axis=-1)
                new_raw = jnp.concatenate(new_raws, axis=-1)
                return (prior_flat, rec_state, new_act), (latent, new_act, new_raw)

            _, (latents, acts, raws) = jax.lax.scan(
                step, (start_prior, start_recurrent, actions0), keys, unroll=imagination_unroll
            )
            trajectories = jnp.concatenate([latent0[None], latents], axis=0)  # [H+1, TB, L]
            im_actions = jnp.concatenate([actions0[None], acts], axis=0)  # [H+1, TB, A]
            im_actions_raw = jnp.concatenate([raw0[None], raws], axis=0)  # [H+1, TB, A]
            return trajectories, im_actions, im_actions_raw

        img_keys = jax.random.split(k_img, horizon)

        def actor_loss_fn(actor_params):
            trajectories, im_actions, im_actions_raw = imagine(actor_params, k_img0, img_keys)
            with _scope("imagination_heads"):
                predicted_values = TwoHotEncodingDistribution(
                    modules.critic.apply(params["critic"], trajectories), dims=1
                ).mean
                predicted_rewards = TwoHotEncodingDistribution(
                    modules.reward_model.apply(new_wm["reward_model"], trajectories), dims=1
                ).mean
                continues = Independent(
                    BernoulliSafeMode(
                        logits=modules.continue_model.apply(new_wm["continue_model"], trajectories)
                    ),
                    1,
                ).base.mode
                continues = jnp.concatenate([true_continue[None], continues[1:]], axis=0)
                lambda_values = compute_lambda_values(
                    predicted_rewards[1:], predicted_values[1:], continues[1:] * gamma, lmbda=lmbda
                )
                discount = jax.lax.stop_gradient(jnp.cumprod(continues * gamma, axis=0) / gamma)

            with _scope("moments"):
                offset, invscale, new_moments = update_moments(
                    moments_state,
                    lambda_values,
                    decay=float(moments_cfg.decay),
                    max_=float(moments_cfg.max),
                    percentile_low=float(moments_cfg.percentile.low),
                    percentile_high=float(moments_cfg.percentile.high),
                )
            baseline = predicted_values[:-1]
            normed_lambda = (lambda_values - offset) / invscale
            normed_baseline = (baseline - offset) / invscale
            advantage = normed_lambda - normed_baseline
            # the actor over the whole imagined trajectory, for the score function:
            # flops.py counts the actor once, the program applies it here again
            with _scope("imagination_actor"):
                policies = ActorOutput(
                    modules.actor, modules.actor.apply(actor_params, jax.lax.stop_gradient(trajectories))
                )
            if is_continuous and actor_objective != "reinforce":
                # reference parity: direct advantage (dynamics backprop) for
                # continuous actions. The walker_walk forensics measured this
                # gradient as noise-dominated at the trained-policy state
                # (key-to-key update cosine ~0, benchmarks/WALKER_WALK_NOTES.md);
                # algo.actor.objective=reinforce opts continuous actors into the
                # low-variance score-function estimator the discrete branch uses
                # (the DreamerV3 paper's own default for all action spaces).
                objective = advantage
            else:
                # score-function estimator: log-prob evaluated at the RAW samples
                # (clipping rescales saturated continuous actions onto the
                # boundary, where the clipped point's log-prob is not the
                # sampled policy's score; discrete raw == clipped)
                splits = np.cumsum(np.asarray(actions_dim))[:-1]
                action_parts = jnp.split(jax.lax.stop_gradient(im_actions_raw), splits, axis=-1)
                log_probs = sum(
                    d.log_prob(a) for d, a in zip(policies.dists, action_parts)
                )  # [H+1, TB]
                objective = log_probs[..., None][:-1] * jax.lax.stop_gradient(advantage)
            try:
                entropy = ent_coef * policies.entropy()
            except NotImplementedError:
                entropy = jnp.zeros(trajectories.shape[:-1], dtype=jnp.float32)
            policy_loss = -jnp.mean(
                jax.lax.stop_gradient(discount[:-1]) * (objective + entropy[..., None][:-1])
            )
            aux_a = {
                "trajectories": trajectories,
                "lambda_values": lambda_values,
                "discount": discount,
                "moments": new_moments,
            }
            return policy_loss, aux_a

        (policy_loss, aux_a), actor_grads = jax.value_and_grad(actor_loss_fn, has_aux=True)(params["actor"])
        with _scope("actor_opt"):
            actor_grad_norm = optax_global_norm(actor_grads)
            actor_updates, actor_opt = actor_tx.update(actor_grads, opt_states.actor, params["actor"])
            new_actor = apply_updates(params["actor"], actor_updates)
            if nonfinite_guard:
                (new_actor, actor_opt), actor_skipped = resilience.finite_or_skip(
                    (policy_loss, actor_grad_norm), (new_actor, actor_opt), (params["actor"], opt_states.actor)
                )
            else:
                actor_skipped = jnp.float32(0.0)

        # ---- critic update (Eq. 10) on the pre-update-actor trajectories
        trajectories = jax.lax.stop_gradient(aux_a["trajectories"])
        lambda_values = jax.lax.stop_gradient(aux_a["lambda_values"])
        discount = aux_a["discount"]

        def critic_loss_fn(critic_params):
            with _scope("critic_update"):
                qv = TwoHotEncodingDistribution(modules.critic.apply(critic_params, trajectories[:-1]), dims=1)
            with _scope("target_critic"):
                predicted_target_values = TwoHotEncodingDistribution(
                    modules.critic.apply(target_critic, trajectories[:-1]), dims=1
                ).mean
            with _scope("critic_update"):
                value_loss = -qv.log_prob(lambda_values) - qv.log_prob(
                    jax.lax.stop_gradient(predicted_target_values)
                )
                return jnp.mean(value_loss * discount[:-1][..., 0])

        value_loss, critic_grads = jax.value_and_grad(critic_loss_fn)(params["critic"])
        with _scope("critic_opt"):
            critic_grad_norm = optax_global_norm(critic_grads)
            critic_updates, critic_opt = critic_tx.update(critic_grads, opt_states.critic, params["critic"])
            new_critic = apply_updates(params["critic"], critic_updates)
            if nonfinite_guard:
                (new_critic, critic_opt), critic_skipped = resilience.finite_or_skip(
                    (value_loss, critic_grad_norm), (new_critic, critic_opt), (params["critic"], opt_states.critic)
                )
            else:
                critic_skipped = jnp.float32(0.0)

        # f32 island: entropy is a sum of p*log p terms over discrete*stoch
        # categories — accumulate in f32 even when the RSSM emits bf16 logits
        # (no-op for f32 runs; the fused kernel path already returns f32 logits)
        post_ent = (
            Independent(OneHotCategorical(logits=aux["posteriors_logits"].astype(jnp.float32)), 1)
            .entropy()
            .mean()
        )
        prior_ent = (
            Independent(OneHotCategorical(logits=aux["priors_logits"].astype(jnp.float32)), 1)
            .entropy()
            .mean()
        )
        new_params = {
            "world_model": new_wm,
            "actor": new_actor,
            "critic": new_critic,
            "target_critic": target_critic,
        }
        metrics = jnp.stack(
            [
                world_loss,
                value_loss,
                policy_loss,
                aux["observation_loss"],
                aux["reward_loss"],
                aux["state_loss"],
                aux["continue_loss"],
                aux["kl"],
                post_ent,
                prior_ent,
                world_grad_norm,
                actor_grad_norm,
                critic_grad_norm,
                # return-normalizer state: the advantage scale divisor is
                # max(1e-8, high-low); its drift is the first thing to check
                # when a policy degrades under a healthy world model+critic
                aux_a["moments"].low,
                aux_a["moments"].high,
                wm_skipped + actor_skipped + critic_skipped,
            ]
        )
        return (new_params, DV3OptStates(world_opt, actor_opt, critic_opt), aux_a["moments"], counter + 1), metrics

    def train(params, opt_states, moments_state, counter, batches, key):
        g = next(iter(batches.values())).shape[0]
        keys = jax.random.split(key, g)
        (params, opt_states, moments_state, counter), metrics = jax.lax.scan(
            one_step, (params, opt_states, moments_state, counter), (batches, keys)
        )
        m = metrics.mean(axis=0)
        named = {
            "Loss/world_model_loss": m[0],
            "Loss/value_loss": m[1],
            "Loss/policy_loss": m[2],
            "Loss/observation_loss": m[3],
            "Loss/reward_loss": m[4],
            "Loss/state_loss": m[5],
            "Loss/continue_loss": m[6],
            "State/kl": m[7],
            "State/post_entropy": m[8],
            "State/prior_entropy": m[9],
            "Grads/world_model": m[10],
            "Grads/actor": m[11],
            "Grads/critic": m[12],
            "State/moments_low": m[13],
            "State/moments_high": m[14],
            "Resilience/nonfinite_skips": metrics[:, 15].sum(),
        }
        # raveled player subset computed in-graph: the host-player refresh is one
        # flat transfer, not a per-leaf pull (see DreamerPlayerSync)
        with _scope("player_ravel"):
            flat_player = psync.ravel(params) if psync is not None else None
        return params, opt_states, moments_state, counter, flat_player, named

    return init_opt, jax_compile.guarded_jit(train, name="dv3.train", donate_argnums=(0, 1, 2))


def optax_global_norm(tree) -> jax.Array:
    import optax

    return optax.global_norm(tree)


def apply_updates(params, updates):
    import optax

    return optax.apply_updates(params, updates)


@register_algorithm()
def main(runtime, cfg: Dict[str, Any]):
    world_size = runtime.world_size
    rank = runtime.global_rank

    state = None
    if cfg.checkpoint.resume_from:
        from sheeprl_tpu.utils.checkpoint import load_state

        state = load_state(cfg.checkpoint.resume_from)

    # These arguments cannot be changed (reference dreamer_v3.py:400-403)
    cfg.env.frame_stack = -1
    if 2 ** int(np.log2(cfg.env.screen_size)) != cfg.env.screen_size:
        raise ValueError(f"The screen size must be a power of 2, got: {cfg.env.screen_size}")

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
    env_fns = [
        make_env(
            cfg,
            cfg.seed + rank * cfg.env.num_envs + i,
            rank * cfg.env.num_envs,
            log_dir if runtime.is_global_zero else None,
            "train",
            vector_env_idx=i,
        )
        for i in range(cfg.env.num_envs)
    ]
    if ft.env_supervision.enabled:
        # WorkerSupervisor supersedes RestartOnException: same restart-on-crash
        # semantics (it emits the same `restart_on_exception` info key the buffer
        # patching below consumes) plus bounded backoff, hang detection via the
        # per-step deadline, and exported restart counters
        envs = resilience.make_supervised_env(env_fns, sync=cfg.env.sync_env, ft=ft)
    else:
        envs = vectorized_env(
            [partial(RestartOnException, fn) for fn in env_fns],
            sync=cfg.env.sync_env,
            step_timeout=ft.env_supervision.step_timeout_s,
        )
    action_space = envs.single_action_space
    observation_space = envs.single_observation_space

    is_continuous = isinstance(action_space, gym.spaces.Box)
    is_multidiscrete = isinstance(action_space, gym.spaces.MultiDiscrete)
    actions_dim = tuple(
        action_space.shape
        if is_continuous
        else (action_space.nvec.tolist() if is_multidiscrete else [action_space.n])
    )
    clip_rewards_fn = (lambda r: np.tanh(r)) if cfg.env.clip_rewards else (lambda r: r)
    if not isinstance(observation_space, gym.spaces.Dict):
        raise RuntimeError(f"Unexpected observation type, should be of type Dict, got: {observation_space}")
    if (
        len(set(cfg.algo.cnn_keys.encoder).intersection(set(cfg.algo.cnn_keys.decoder))) == 0
        and len(set(cfg.algo.mlp_keys.encoder).intersection(set(cfg.algo.mlp_keys.decoder))) == 0
    ):
        raise RuntimeError("The CNN keys or the MLP keys of the encoder and decoder must not be disjointed")
    if len(set(cfg.algo.cnn_keys.decoder) - set(cfg.algo.cnn_keys.encoder)) > 0:
        raise RuntimeError(
            "The CNN keys of the decoder must be contained in the encoder ones. "
            f"Those keys are decoded without being encoded: {list(set(cfg.algo.cnn_keys.decoder))}"
        )
    if len(set(cfg.algo.mlp_keys.decoder) - set(cfg.algo.mlp_keys.encoder)) > 0:
        raise RuntimeError(
            "The MLP keys of the decoder must be contained in the encoder ones. "
            f"Those keys are decoded without being encoded: {list(set(cfg.algo.mlp_keys.decoder))}"
        )
    if cfg.metric.log_level > 0:
        runtime.print("Encoder CNN keys:", cfg.algo.cnn_keys.encoder)
        runtime.print("Encoder MLP keys:", cfg.algo.mlp_keys.encoder)
        runtime.print("Decoder CNN keys:", cfg.algo.cnn_keys.decoder)
        runtime.print("Decoder MLP keys:", cfg.algo.mlp_keys.decoder)
    obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)

    modules, params, player = build_agent(
        runtime,
        actions_dim,
        is_continuous,
        cfg,
        observation_space,
        state["world_model"] if state else None,
        state["actor"] if state else None,
        state["critic"] if state else None,
        state["target_critic"] if state else None,
    )

    psync = DreamerPlayerSync(
        runtime, params, wm_keys=PLAYER_WM_KEYS, every=cfg.algo.get("player_sync_every", 1)
    )
    init_opt, train_fn = make_train_fn(modules, cfg, runtime, is_continuous, actions_dim, psync)
    opt_states = init_opt(params)
    if state:
        opt_states = jax.tree_util.tree_map(jnp.asarray, state["opt_states"])
    moments_state = init_moments()
    if state and "moments" in state:
        moments_state = MomentsState(*[jnp.asarray(v) for v in state["moments"]])
    counter = jnp.int32(state["counter"]) if state and "counter" in state else jnp.int32(0)
    params = runtime.place_params(params)
    opt_states = runtime.place_params(opt_states)
    # the player must never hold mesh-resident params when it lives on the host
    # CPU backend: its per-step calls would pay per-leaf cross-backend pulls
    psync.push(player, params, force=True)

    if runtime.is_global_zero:
        save_configs(cfg, log_dir)

    aggregator = None
    if not MetricAggregator.disabled:
        aggregator = instantiate(cfg.metric.aggregator)

    rb, prefetcher = make_sequential_replay(cfg, runtime, log_dir, obs_keys)
    if state and cfg.buffer.checkpoint and "rb" in state:
        rb.load_state_dict(state["rb"])

    train_step = 0
    last_train = 0
    train_calls = 0
    start_iter = (state["iter_num"] // world_size) + 1 if state else 1
    policy_step = state["iter_num"] * cfg.env.num_envs if state else 0
    last_log = state["last_log"] if state else 0
    last_checkpoint = state["last_checkpoint"] if state else 0
    policy_steps_per_iter = int(cfg.env.num_envs * world_size)
    total_iters = int(cfg.algo.total_steps // policy_steps_per_iter) if not cfg.dry_run else 1
    learning_starts = cfg.algo.learning_starts // policy_steps_per_iter if not cfg.dry_run else 0
    prefill_steps = learning_starts - int(learning_starts > 0)
    if state:
        cfg.algo.per_rank_batch_size = state["batch_size"] // world_size
        learning_starts += start_iter
        prefill_steps += start_iter

    ratio = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
    if state:
        ratio.load_state_dict(state["ratio"])


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
    if state and "rng" in state:
        rng = jnp.asarray(state["rng"])
    step_data: Dict[str, np.ndarray] = {}
    obs = envs.reset(seed=cfg.seed)[0]
    for k in obs_keys:
        step_data[k] = np.asarray(obs[k])[np.newaxis]
    step_data["rewards"] = np.zeros((1, cfg.env.num_envs, 1))
    step_data["truncated"] = np.zeros((1, cfg.env.num_envs, 1))
    step_data["terminated"] = np.zeros((1, cfg.env.num_envs, 1))
    step_data["is_first"] = np.ones_like(step_data["terminated"])
    player.init_states()

    # ----- software pipeline (core/pipeline.py): env workers step while the host
    # writes the pre-step buffer row (the prefetcher lock wait hides behind the
    # env step); obs reach the device as ONE packed put per step
    stepper = AsyncEnvStepper(envs, enabled=pipeline_enabled(cfg))
    codec = PackedObsCodec(
        cnn_keys=cfg.algo.cnn_keys.encoder,
        device=runtime.player_device,
        leading_dims=(1, cfg.env.num_envs),
    )

    # ----- AOT warmup (core/compile.py): compile the packed policy step, the
    # fused world-model/actor/critic train step (for every gradient-step count
    # the Ratio schedule will request) and the metric-drain kernels on a
    # background thread while the prefill rollout collects; the first train
    # call then executes a pre-built executable (trace count 0 at call time).
    warmup = jax_compile.AOTWarmup(enabled=jax_compile.aot_enabled(cfg))
    if warmup.enabled:
        packed0 = codec.encode(obs)
        act_fn = player.packed_step_fn(codec)
        act_specs = (
            jax_compile.specs_of(player.wm_params),
            jax_compile.specs_of(player.actor_params),
            jax_compile.specs_of(player.state),
            jax_compile.spec_like(packed0),
            jax_compile.spec_like(rng),
        )
        warmup.add(act_fn, *act_specs)
        # The recurrent/stochastic state's dtype differs between the reset
        # state (f32 zeros from init_states) and the step's own output (the
        # model's compute dtype, e.g. bf16), and episode resets flip it back:
        # warm the steady-state signature too or step #2 retraces every run.
        _acts_out, state_out = jax.eval_shape(act_fn.fun, *act_specs)
        steady_specs = (
            act_specs[0],
            act_specs[1],
            jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), state_out),
            act_specs[3],
            act_specs[4],
        )
        if jax_compile.abstract_signature(steady_specs, {}) != jax_compile.abstract_signature(
            act_specs, {}
        ):
            warmup.add(act_fn, *steady_specs)
        # The train step's leading batch dim is the per-iteration gradient-step
        # count: predict the counts the Ratio schedule will yield by replaying
        # the loop's exact arithmetic on a clone (the schedule is periodic
        # after the first few train iterations, so 1024 iterations and 4
        # distinct counts bound the sweep).
        clone = Ratio(cfg.algo.replay_ratio, pretrain_steps=cfg.algo.per_rank_pretrain_steps)
        clone.load_state_dict(ratio.state_dict())
        unique_g = []
        sim_policy_step = policy_step
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for sim_iter in range(start_iter, min(total_iters, start_iter + 1024) + 1):
                sim_policy_step += policy_steps_per_iter
                if sim_iter >= learning_starts:
                    g = clone((sim_policy_step - prefill_steps * policy_steps_per_iter) / world_size)
                    if g > 0 and g not in unique_g:
                        unique_g.append(g)
                        if len(unique_g) >= 4:
                            break
        # batch specs mirror the prefetcher's output: [G, L, B, *feat] on the
        # data axis, storage dtypes narrowed exactly like get_array's transfer
        seq_len = int(cfg.algo.per_rank_sequence_length)
        bsz = int(cfg.algo.per_rank_batch_size) * world_size
        batch_sharding = NamedSharding(runtime.mesh, P(None, None, "data"))
        feat = {k: tuple(step_data[k].shape[2:]) for k in obs_keys}
        store_dtype = {k: step_data[k].dtype for k in obs_keys}
        for k in ("rewards", "truncated", "terminated", "is_first"):
            feat[k] = (1,)
            store_dtype[k] = step_data[k].dtype
        feat["actions"] = (int(np.sum(actions_dim)),)
        store_dtype["actions"] = np.dtype(np.float32)
        for g in unique_g:
            batches_spec = {
                k: jax.ShapeDtypeStruct(
                    (g, seq_len, bsz, *feat[k]),
                    NUMPY_TO_JAX_DTYPE.get(np.dtype(store_dtype[k]), jnp.float32),
                    sharding=batch_sharding,
                )
                for k in feat
            }
            warmup.add(
                train_fn,
                jax_compile.specs_of(params),
                jax_compile.specs_of(opt_states),
                jax_compile.specs_of(moments_state),
                jax_compile.spec_like(counter),
                batches_spec,
                jax_compile.spec_like(rng),
            )
        if aggregator is not None:
            warmup.add_task(
                lambda: aggregator.precompile_drain(
                    (
                        "Loss/world_model_loss",
                        "Loss/value_loss",
                        "Loss/policy_loss",
                        "Loss/observation_loss",
                        "Loss/reward_loss",
                        "Loss/state_loss",
                        "Loss/continue_loss",
                        "State/kl",
                        "State/post_entropy",
                        "State/prior_entropy",
                        "Grads/world_model",
                        "Grads/actor",
                        "Grads/critic",
                        "State/moments_low",
                        "State/moments_high",
                        "Resilience/nonfinite_skips",
                    ),
                    sharding=runtime.replicated,
                ),
                name="metric.drain",
            )
        warmup.start()

    cumulative_per_rank_gradient_steps = 0
    heartbeat_t0, heartbeat_iter = time.perf_counter(), start_iter

    def _save_checkpoint():
        # shared by the periodic checkpoint and the preemption emergency save so
        # both are resumable through the identical path; the rng chain makes the
        # resumed action/train key sequence identical to an uninterrupted run
        ckpt_state = {
            "world_model": jax.device_get(params["world_model"]),
            "actor": jax.device_get(params["actor"]),
            "critic": jax.device_get(params["critic"]),
            "target_critic": jax.device_get(params["target_critic"]),
            "opt_states": jax.device_get(opt_states),
            "moments": tuple(np.asarray(v) for v in moments_state),
            "counter": int(counter),
            "ratio": ratio.state_dict(),
            "iter_num": iter_num * world_size,
            "batch_size": cfg.algo.per_rank_batch_size * world_size,
            "last_log": last_log,
            "last_checkpoint": last_checkpoint,
            "rng": jax.device_get(rng),
        }
        ckpt_path = os.path.join(log_dir, f"checkpoint/ckpt_{policy_step}_{rank}.ckpt")
        runtime.call(
            "on_checkpoint_coupled",
            ckpt_path=ckpt_path,
            state=ckpt_state,
            replay_buffer=rb if cfg.buffer.checkpoint else None,
            io_lock=prefetcher.guard(),
            healthy=sentinel.certifiable,
            policy_step=policy_step,
        )

    guard = resilience.PreemptionGuard(
        enabled=ft.preemption.enabled, stop_after_iters=ft.preemption.stop_after_iters
    )
    with guard:
        for iter_num in range(start_iter, total_iters + 1):
            profiler.step(policy_step)
            policy_step += policy_steps_per_iter
            if iter_num % 100 == 0 and iter_num > heartbeat_iter:
                now = time.perf_counter()
                runtime.print(
                    f"[hb] iter={iter_num}/{total_iters} policy_step={policy_step} "
                    f"({(iter_num - heartbeat_iter) / (now - heartbeat_t0):.2f} it/s)",
                    flush=True,
                )
                heartbeat_t0, heartbeat_iter = now, iter_num

            with timer("Time/env_interaction_time", SumMetric()):
                if iter_num <= learning_starts and state is None and "minedojo" not in cfg.env.wrapper._target_.lower():
                    real_actions = actions = np.array(envs.action_space.sample())
                    if not is_continuous:
                        actions = np.concatenate(
                            [
                                np.eye(act_dim, dtype=np.float32)[act.reshape(-1)]
                                for act, act_dim in zip(actions.reshape(len(actions_dim), -1), actions_dim)
                            ],
                            axis=-1,
                        )
                else:
                    # ONE packed host->device transfer per step: unpack, normalize
                    # and action-mask extraction run in-graph (PlayerDV3.get_actions_packed)
                    packed = codec.encode(obs)
                    rng, act_key = jax.random.split(rng)
                    actions_list = player.get_actions_packed(codec, packed, act_key)
                    actions = np.concatenate([np.asarray(a) for a in actions_list], axis=-1)
                    if is_continuous:
                        real_actions = actions
                    else:
                        real_actions = np.stack(
                            [np.asarray(a).argmax(axis=-1) for a in actions_list], axis=-1
                        )

                stepper.step_async(real_actions.reshape(envs.action_space.shape))

                # ---- overlap window: env workers are stepping; the pre-step row
                # write (and any wait on the prefetcher's sample lock) hides here
                step_data["actions"] = actions.reshape((1, cfg.env.num_envs, -1))
                with prefetcher.guard():  # no torn rows under the worker's concurrent sample
                    rb.add(step_data, validate_args=cfg.buffer.validate_args)

                next_obs, rewards, terminated, truncated, infos = stepper.step_wait()
                dones = np.logical_or(terminated, truncated).astype(np.uint8)

            step_data["is_first"] = np.zeros_like(step_data["terminated"])
            if "restart_on_exception" in infos:
                for i, agent_roe in enumerate(infos["restart_on_exception"]):
                    if agent_roe and not dones[i]:
                        # crash-restart boundary: the last stored transition becomes a
                        # truncation (works on host and HBM buffers alike)
                        with prefetcher.guard():  # no torn flags under the worker's sample
                            rb.patch_last([i], {"terminated": 0.0, "truncated": 1.0, "is_first": 0.0})
                        step_data["is_first"][0, i] = np.ones_like(step_data["is_first"][0, i])

            if cfg.metric.log_level > 0:
                for i, (ep_rew, ep_len) in enumerate(finished_episodes(infos)):
                    if aggregator:
                        if "Rewards/rew_avg" in aggregator:
                            aggregator.update("Rewards/rew_avg", ep_rew)
                        if "Game/ep_len_avg" in aggregator:
                            aggregator.update("Game/ep_len_avg", ep_len)
                    runtime.print(f"Rank-0: policy_step={policy_step}, reward_env_{i}={ep_rew}")

            # Save the real next observation (terminal obs for autoreset envs)
            real_next_obs = {k: np.asarray(v).copy() for k, v in next_obs.items() if k in obs_keys}
            finals = final_observations(infos, obs_keys)
            if finals:
                for idx, final_obs in finals.items():
                    for k, v in final_obs.items():
                        real_next_obs[k][idx] = v

            for k in obs_keys:
                step_data[k] = np.asarray(next_obs[k])[np.newaxis]
            obs = next_obs

            rewards = np.asarray(rewards, dtype=np.float32).reshape((1, cfg.env.num_envs, -1))
            step_data["terminated"] = np.asarray(terminated, dtype=np.float32).reshape((1, cfg.env.num_envs, -1))
            step_data["truncated"] = np.asarray(truncated, dtype=np.float32).reshape((1, cfg.env.num_envs, -1))
            step_data["rewards"] = clip_rewards_fn(rewards)

            dones_idxes = dones.nonzero()[0].tolist()
            reset_envs = len(dones_idxes)
            if reset_envs > 0:
                reset_data = {}
                for k in obs_keys:
                    reset_data[k] = (real_next_obs[k][dones_idxes])[np.newaxis]
                reset_data["terminated"] = step_data["terminated"][:, dones_idxes]
                reset_data["truncated"] = step_data["truncated"][:, dones_idxes]
                reset_data["actions"] = np.zeros((1, reset_envs, int(np.sum(actions_dim))))
                reset_data["rewards"] = step_data["rewards"][:, dones_idxes]
                reset_data["is_first"] = np.zeros_like(reset_data["terminated"])
                with prefetcher.guard():
                    rb.add(reset_data, dones_idxes, validate_args=cfg.buffer.validate_args)

                step_data["rewards"][:, dones_idxes] = np.zeros_like(reset_data["rewards"])
                step_data["terminated"][:, dones_idxes] = np.zeros_like(step_data["terminated"][:, dones_idxes])
                step_data["truncated"][:, dones_idxes] = np.zeros_like(step_data["truncated"][:, dones_idxes])
                step_data["is_first"][:, dones_idxes] = np.ones_like(step_data["is_first"][:, dones_idxes])
                player.init_states(dones_idxes)

            # ---- training phase
            if iter_num >= learning_starts:
                ratio_steps = policy_step - prefill_steps * policy_steps_per_iter
                per_rank_gradient_steps = ratio(ratio_steps / world_size)
                if per_rank_gradient_steps > 0 and sentinel.ratio_scale < 1.0:
                    # health-sentinel backoff: shrink this round's gradient grant
                    per_rank_gradient_steps = max(1, int(per_rank_gradient_steps * sentinel.ratio_scale))
                if per_rank_gradient_steps > 0:
                    # one span for the train call; the prefetcher's, the guarded
                    # function's and the player sync's own spans nest under it
                    with trace.span("train.call", step=train_calls, gradient_steps=per_rank_gradient_steps):
                        # steady-state: this consumes the batch prefetched during the previous
                        # train step and immediately starts speculating the next one
                        batches = prefetcher.get(
                            batch_size=cfg.algo.per_rank_batch_size * world_size,
                            sequence_length=cfg.algo.per_rank_sequence_length,
                            n_samples=per_rank_gradient_steps,
                        )
                        with timer("Time/train_time", SumMetric()):
                            # no-op once the warmup thread finished (first train
                            # call at the latest; usually hidden behind prefill)
                            warmup.wait()
                            rng, train_key = jax.random.split(rng)
                            params, opt_states, moments_state, counter, flat_player, train_metrics = train_fn(
                                params, opt_states, moments_state, counter, batches, train_key
                            )
                            if not timer.disabled:
                                # fence ONLY when timing: Time/train_time must include the
                                # device work, but an unconditional sync would serialize the
                                # loop on the dispatch round-trip
                                with trace.span("train.fence"):
                                    jax.block_until_ready(params)
                            psync.push(player, params, flat=flat_player)
                            cumulative_per_rank_gradient_steps += per_rank_gradient_steps
                            train_step += world_size * per_rank_gradient_steps
                            train_calls += 1
                    if aggregator:
                        aggregator.update_from_device(train_metrics)
                    resilience.enforce_nonfinite_policy(ft, train_metrics)
            env_deltas = resilience.drain_env_counters(envs, aggregator)
            jax_compile.drain_compile_counters(aggregator)
            if cumulative_per_rank_gradient_steps > 0 and not jax_compile.is_steady():
                # steady-state watermark: the first real train iteration has
                # compiled everything; any retrace from here is a perf cliff
                jax_compile.mark_steady()

            # ----- health sentinel: warn -> backoff (ratio grant above) -> rollback
            action = sentinel.observe(
                policy_step,
                train_metrics=train_metrics if "train_metrics" in dir() else None,
                env_counters=env_deltas,
            )
            if action.rollback:
                rb_state = sentinel.take_rollback_state(os.path.join(log_dir, "checkpoint"))
                if rb_state is not None:
                    params = runtime.place_params(
                        {
                            **params,
                            "world_model": jax.tree_util.tree_map(jnp.asarray, rb_state["world_model"]),
                            "actor": jax.tree_util.tree_map(jnp.asarray, rb_state["actor"]),
                            "critic": jax.tree_util.tree_map(jnp.asarray, rb_state["critic"]),
                            "target_critic": jax.tree_util.tree_map(jnp.asarray, rb_state["target_critic"]),
                        }
                    )
                    opt_states = runtime.place_params(
                        jax.tree_util.tree_map(jnp.asarray, rb_state["opt_states"])
                    )
                    moments_state = MomentsState(*[jnp.asarray(v) for v in rb_state["moments"]])
                    counter = jnp.int32(rb_state["counter"])
                    ratio.load_state_dict(rb_state["ratio"])
                    if "rng" in rb_state:
                        rng = jnp.asarray(rb_state["rng"])
                    # replay rows stay valid off-policy data; only the learner
                    # (and the player's copy of it) rewinds to the snapshot
                    psync.push(player, params, force=True)
                    runtime.print(
                        f"Health rollback at policy_step={policy_step}: restored certified "
                        "checkpoint, training continues."
                    )
            sentinel.drain(aggregator)

            # ---- logging
            if cfg.metric.log_level > 0 and (policy_step - last_log >= cfg.metric.log_every or iter_num == total_iters):
                overlap_s, overlap_steps = stepper.drain_overlap()
                if overlap_s > 0:
                    sps_overlap = overlap_steps * cfg.env.num_envs * cfg.env.action_repeat / overlap_s
                    if aggregator and "Time/sps_pipeline_overlap" in aggregator:
                        aggregator.update("Time/sps_pipeline_overlap", sps_overlap)
                    elif logger:
                        logger.log_metrics({"Time/sps_pipeline_overlap": sps_overlap}, policy_step)
                if aggregator and not aggregator.disabled:
                    logger.log_metrics(aggregator.compute(), policy_step)
                    aggregator.reset()
                if logger and policy_step > 0:
                    logger.log_metrics(
                        {"Params/replay_ratio": cumulative_per_rank_gradient_steps * world_size / policy_step},
                        policy_step,
                    )
                if not timer.disabled:
                    timer_metrics = timer.compute()
                    if logger and timer_metrics.get("Time/train_time", 0) > 0:
                        logger.log_metrics(
                            {"Time/sps_train": (train_step - last_train) / timer_metrics["Time/train_time"]},
                            policy_step,
                        )
                        # no Time/mfu row here: cost_analysis counts a lax.scan body
                        # once, and this program is scans (howto/observability.md)
                    if logger and timer_metrics.get("Time/env_interaction_time", 0) > 0:
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

            # ---- checkpoint
            if (cfg.checkpoint.every > 0 and policy_step - last_checkpoint >= cfg.checkpoint.every) or (
                iter_num == total_iters and cfg.checkpoint.save_last
            ):
                last_checkpoint = policy_step
                _save_checkpoint()

            guard.completed_iteration()
            if guard.should_stop:
                if last_checkpoint != policy_step:  # periodic save above already covered this step
                    last_checkpoint = policy_step
                    _save_checkpoint()
                runtime.print(
                    f"Preemption ({guard.describe()}) at iteration {iter_num}: emergency "
                    "checkpoint saved, exiting cleanly for resume."
                )
                break

    prefetcher.close()
    profiler.close()
    envs.close()
    if runtime.is_global_zero and cfg.algo.run_test:
        psync.push(player, params, force=True)  # the cadence may have left the player stale
        test(player, runtime, cfg, log_dir, greedy=False)
    if logger:
        logger.finalize()
