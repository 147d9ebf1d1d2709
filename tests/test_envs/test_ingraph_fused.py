"""Whole-iteration fused in-graph training (envs/ingraph/fused.py).

Pins the tentpole guarantees:
- fused-vs-split BIT-parity: the fused iteration inlines the collector's
  ``collect_impl`` and the algo's ``make_update_impl`` output — the same
  expressions the split path jits separately — so params, trajectories, and
  losses must agree bit-for-bit, per iteration, on CartPole and GridWorld;
- a warm fused iteration performs metrics-only host traffic (the whole
  rollout + GAE + update epochs run under ``jax.transfer_guard("disallow")``);
- the ``shard_map`` variant trains on a 2-device mesh without retracing;
- the ``train.fused_update`` chaos seam fires on the fused path;
- the SAC replay-ring wiring trains end-to-end through the real CLI.

Every split/fused pair in one process needs SEPARATE collector (and env)
instances: ``lax.scan`` caches the body jaxpr keyed on the body function
object, so tracing both paths over one collector's shared ``one_step``
closure replays the first trace's captured param tracers into the second
(UnexpectedTracerError). Production processes only ever trace one path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.algos.ppo.agent import build_agent
from sheeprl_tpu.algos.ppo.ppo import make_train_fn, make_update_impl
from sheeprl_tpu.config import instantiate, load_config
from sheeprl_tpu.core import failpoints
from sheeprl_tpu.core.runtime import build_runtime
from sheeprl_tpu.envs import ingraph as ig
from sheeprl_tpu.utils.optim import with_clipping
from sheeprl_tpu.utils.utils import PlayerParamsSync

pytestmark = pytest.mark.ingraph

N_ENVS = 16
T = 8
N_DATA = N_ENVS * T


def _load_cfg(env_name: str, extra=()):
    return load_config(
        overrides=[
            "exp=ppo",
            f"env={env_name}",
            f"env.num_envs={N_ENVS}",
            f"algo.rollout_steps={T}",
            f"algo.per_rank_batch_size={N_DATA // 2}",
            "algo.update_epochs=2",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
            "seed=7",
            *extra,
        ]
    )


def _build_stack(cfg, runtime, name: str):
    """One independent (venv, agent, optimizer, collector) world; building it
    twice from the same cfg reproduces identical init bits on both sides."""
    import gymnasium as gym

    venv = ig.make_vector_env(cfg, N_ENVS, cfg.seed, device=runtime.device)
    space = venv.single_action_space
    is_continuous = isinstance(space, gym.spaces.Box)
    actions_dim = (
        tuple(space.shape) if is_continuous else (int(space.n),)
    )
    agent, params, player = build_agent(
        runtime, actions_dim, is_continuous, cfg, venv.single_observation_space, None
    )
    player.params = jax.device_put(player.params, runtime.device)
    venv.reset(seed=cfg.seed)
    collector = ig.InGraphRolloutCollector(
        venv, player, rollout_steps=T, gamma=float(cfg.algo.gamma), name=name
    )
    tx = with_clipping(instantiate(dict(cfg.algo.optimizer))(), cfg.algo.max_grad_norm)
    # placed like the loop places it (ppo.py): optax's step counter is born
    # uncommitted on the default device, and an operand that changes placement
    # between the first and second call is a jit-cache miss, i.e. a retrace
    opt_state = runtime.place_params(tx.init(params))
    params_sync = PlayerParamsSync(player.params)
    return venv, agent, params, player, collector, tx, opt_state, params_sync


def _extras(cfg):
    return (
        jnp.float32(cfg.algo.clip_coef),
        jnp.float32(cfg.algo.ent_coef),
        jnp.float32(1.0),
    )


@pytest.mark.timeout(300)
@pytest.mark.parametrize("env_name", ["jax_cartpole", "jax_gridworld"])
def test_fused_matches_split_bitwise(env_name):
    cfg = _load_cfg(env_name)
    runtime = build_runtime(cfg.fabric)
    extras = _extras(cfg)

    # ----- split reference: jitted collect, then the jitted train step
    venv_s, agent_s, params_s, player_s, collector_s, tx_s, opt_s, sync_s = _build_stack(
        cfg, runtime, "split"
    )
    train_fn = make_train_fn(agent_s, tx_s, cfg, runtime, N_DATA, ["state"], [], sync_s)
    split_rolls, split_trains = [], []
    for i in range(2):
        player_s.params = params_s  # the loop's params_sync refresh, bit-exact
        data, roll_metrics, next_values = collector_s.collect()
        key = jax.random.fold_in(jax.random.PRNGKey(99), i)
        params_s, opt_s, _flat, train_metrics = train_fn(
            params_s, opt_s, data, next_values, key, *extras
        )
        split_rolls.append(jax.tree_util.tree_map(np.asarray, roll_metrics))
        split_trains.append({k: np.asarray(v) for k, v in train_metrics.items()})

    # ----- fused path on a fresh identical world (same seeds => same bits)
    venv_f, agent_f, params_f, _player_f, collector_f, tx_f, opt_f, sync_f = _build_stack(
        cfg, runtime, "fused"
    )
    update_impl = make_update_impl(agent_f, tx_f, cfg, runtime, N_DATA, ["state"], [], sync_f)
    trainer = ig.FusedInGraphTrainer(collector_f, update_impl, n_extras=3, name="paritytest")
    for i in range(2):
        key = jax.random.fold_in(jax.random.PRNGKey(99), i)
        params_f, opt_f, _flat, roll_metrics, train_metrics = trainer.step(
            params_f, opt_f, key, *extras
        )
        fused_roll = jax.tree_util.tree_map(np.asarray, roll_metrics)
        for k, v in split_rolls[i].items():
            np.testing.assert_array_equal(fused_roll[k], v, err_msg=f"iter {i} roll {k}")
        for k, v in split_trains[i].items():
            np.testing.assert_array_equal(
                np.asarray(train_metrics[k]), v, err_msg=f"iter {i} train {k}"
            )

    # post-update params AND the env carry chain are bit-identical
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params_s,
        params_f,
    )
    np.testing.assert_array_equal(np.asarray(venv_s.carry.obs), np.asarray(venv_f.carry.obs))
    venv_s.close()
    venv_f.close()


@pytest.mark.timeout(300)
def test_fused_iteration_makes_zero_host_transfers():
    """A warm fused iteration — rollout scan + GAE + every update epoch — runs
    under ``jax.transfer_guard("disallow")``: no per-phase host pulls, no
    implicit uploads; the episode/loss metric pulls happen on demand AFTER the
    guard lifts. The guard is proven live by the explicit upload raising."""
    cfg = _load_cfg("jax_cartpole")
    runtime = build_runtime(cfg.fabric)
    venv, agent, params, _player, collector, tx, opt_state, sync = _build_stack(
        cfg, runtime, "zt_fused"
    )
    update_impl = make_update_impl(agent, tx, cfg, runtime, N_DATA, ["state"], [], sync)
    trainer = ig.FusedInGraphTrainer(collector, update_impl, n_extras=3, name="zt_fused")
    extras = _extras(cfg)
    # index the key batch OUTSIDE the guard (x[i] uploads the host index)
    k0, k1, k2 = (k for k in jax.random.split(jax.random.PRNGKey(5), 3))

    params, opt_state, flat, _r, _t = trainer.step(params, opt_state, k0, *extras)
    jax.block_until_ready(flat)

    with jax.transfer_guard("disallow"):
        params, opt_state, flat, roll_metrics, train_metrics = trainer.step(
            params, opt_state, k1, *extras
        )
        # carry chains device-to-device across iterations
        params, opt_state, flat, roll_metrics, train_metrics = trainer.step(
            params, opt_state, k2, *extras
        )
        jax.block_until_ready(flat)  # fence only — not a transfer
        with pytest.raises(Exception):
            jnp.add(flat, 1.0)  # implicit host->device upload: guard is live

    assert np.isfinite(np.asarray(train_metrics["Loss/policy_loss"]))
    assert np.asarray(roll_metrics["dones"]).shape == (T, N_ENVS)
    assert np.asarray(flat).ndim == 1  # the one-transfer player refresh vector
    venv.close()


@pytest.mark.timeout(300)
def test_fused_sharded_two_device_mesh():
    """The shard_map variant: env batch on the ``data`` axis, pmean'd grads,
    replicated params — two steady-state steps, zero retraces, [T, B] episode
    metrics reassembled across shards."""
    if len(jax.local_devices()) < 2:
        pytest.skip("needs >= 2 local devices (conftest forces 8 on CPU)")
    cfg = _load_cfg("jax_cartpole", extra=["fabric.devices=2"])
    runtime = build_runtime(cfg.fabric)
    assert runtime.world_size == 2
    venv, agent, params, _player, collector, tx, opt_state, sync = _build_stack(
        cfg, runtime, "sharded"
    )
    update_impl = make_update_impl(
        agent, tx, cfg, runtime, N_DATA, ["state"], [], sync, axis_name="data", shards=2
    )
    trainer = ig.FusedInGraphTrainer(
        collector, update_impl, n_extras=3, mesh=runtime.mesh, name="shardedtest"
    )
    trainer.shard_carry()
    extras = tuple(trainer.to_mesh(e) for e in _extras(cfg))
    key = trainer.to_mesh(jax.random.PRNGKey(11))
    for i in range(3):
        key_i = trainer.to_mesh(jax.random.fold_in(key, i))
        params, opt_state, flat, roll_metrics, train_metrics = trainer.step(
            params, opt_state, key_i, *extras
        )
    assert trainer.step_fn.retraces == 0, "sharded fused step retraced"
    assert np.asarray(roll_metrics["dones"]).shape == (T, N_ENVS)
    assert np.isfinite(np.asarray(train_metrics["Loss/value_loss"]))
    assert all(np.all(np.isfinite(np.asarray(x))) for x in jax.tree_util.tree_leaves(params))
    venv.close()


@pytest.mark.faults
def test_fused_update_failpoint_covers_fused_path(standard_args, tmp_path, monkeypatch):
    """The ``train.fused_update`` chaos seam fires once per fused iteration,
    BEFORE the compiled step — a raise surfaces out of the real CLI run."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu.cli import run

    args = standard_args + [
        "exp=ppo",
        "env=jax_cartpole",
        "env.num_envs=4",
        "algo.rollout_steps=2",
        "algo.per_rank_batch_size=8",
        "algo.update_epochs=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "buffer.memmap=False",
    ]
    with failpoints.active("train.fused_update:raise:chaos-fused"):
        with pytest.raises(failpoints.FailpointError, match="chaos-fused"):
            run(overrides=args)


@pytest.mark.timeout(480)
def test_sac_ingraph_replay_ring_end_to_end(standard_args, tmp_path, monkeypatch):
    """SAC on the ingraph backend: uniform-action prefill into the HBM replay
    ring, then fused collect+update iterations sampling the ring in-graph —
    through the real CLI (exp=sac pins a LunarLander id, so env.id is
    re-pointed at the in-graph Pendulum port)."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu.cli import run

    args = standard_args + [
        "exp=sac",
        "env=jax_pendulum",
        "env.id=Pendulum-v1",
        "env.num_envs=4",
        "dry_run=False",
        "algo.total_steps=96",
        "algo.ingraph_collect_steps=4",
        "algo.learning_starts=32",
        "algo.per_rank_batch_size=16",
        "algo.hidden_size=8",
        "algo.run_test=False",
        "buffer.size=512",
        "buffer.memmap=False",
        "metric.disable_timer=True",
    ]
    run(overrides=args)
