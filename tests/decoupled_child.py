"""Child process for the cross-process decoupled PPO test (test_multihost.py).

Run as: python tests/decoupled_child.py <coordinator_port> <process_id> <num_processes> <tmpdir>

A 2-process world with 2 CPU devices each (4 global devices). The decoupled
role split is taken over the GLOBAL device set via split_runtime_crosshost:
global device 0 (on process 0) is the player, the remaining 3 devices — one on
process 0 and both of process 1 — form the cross-process trainer mesh. One full
decoupled PPO round runs twice:

  player process collects a (fabricated, seeded) host rollout
    -> CrossHostTransport.rollout_to_trainers (one device broadcast collective
       + local placement on the trainer mesh; the reference pipes this through
       torch scatter_object_list, ppo_decoupled.py:294-310)
    -> the REAL jitted PPO optimization phase (make_train_fn) over the
       3-device cross-process mesh
    -> CrossHostTransport.params_to_player: local D2D refresh onto the player
       chip (reference: flattened-vector NCCL broadcast, :550-554)

Prints one JSON line; the parent asserts params actually changed, all
processes hold bit-identical post-update params, and the player refresh
matches the trainer params exactly.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = [f for f in os.environ.get("XLA_FLAGS", "").split() if "host_platform_device_count" not in f]
flags.append("--xla_force_host_platform_device_count=2")
os.environ["XLA_FLAGS"] = " ".join(flags)

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sheeprl_tpu.core.runtime import enable_cpu_collectives  # noqa: E402

enable_cpu_collectives()  # gloo: CPU cross-process collectives (before backend init)


def main() -> None:
    port, pid, nproc = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    if os.environ.get("XH_DEBUG"):  # dump a stack if a collective wedges this process
        import faulthandler

        faulthandler.dump_traceback_later(int(os.environ["XH_DEBUG"]), exit=True, file=sys.stderr)
    jax.distributed.initialize(f"localhost:{port}", num_processes=nproc, process_id=pid)

    import gymnasium as gym
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.algos.ppo.ppo import make_train_fn
    from sheeprl_tpu.config import instantiate
    from sheeprl_tpu.config.loader import load_config
    from sheeprl_tpu.core.runtime import Runtime
    from sheeprl_tpu.parallel import split_runtime_crosshost
    from sheeprl_tpu.utils.optim import with_clipping

    runtime = Runtime(accelerator="cpu", devices=jax.device_count(), multihost=True)
    player_rt, trainer_rt, transport = split_runtime_crosshost(runtime)
    assert trainer_rt.world_size == 3, trainer_rt.world_size
    assert transport.is_player_process == (pid == 0)

    rollout_steps, n_envs = 4, 3  # n_data = 12 = one global minibatch (4 * 3 trainers)
    cfg = load_config(
        overrides=[
            "exp=ppo",
            "env=dummy",
            "env.num_envs=3",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
            f"algo.rollout_steps={rollout_steps}",
            "algo.per_rank_batch_size=4",
            "algo.update_epochs=1",
            "algo.mlp_layers=1",
            "algo.dense_units=8",
            "fabric.devices=2",
        ]
    )
    obs_space = gym.spaces.Dict({"state": gym.spaces.Box(-1, 1, (5,), np.float32)})
    actions_dim = (4,)
    agent, params, _player = build_agent(trainer_rt, actions_dim, False, cfg, obs_space)
    tx = with_clipping(instantiate(dict(cfg.algo.optimizer))(), cfg.algo.max_grad_norm)
    # params are already trainer-mesh-replicated globals, so optax init's eager
    # zeros_like inherits that placement — re-placing through device_put would
    # trigger jax's per-leaf cross-process equality allgather for nothing
    opt_state = tx.init(params)
    n_data = rollout_steps * n_envs
    train_fn = make_train_fn(agent, tx, cfg, trainer_rt, n_data, ["state"], [])

    params_before = np.concatenate(
        [np.asarray(leaf.addressable_data(0)).ravel() for leaf in jax.tree_util.tree_leaves(params)]
    )

    rng = np.random.default_rng(7)  # both processes build templates; only pid 0's VALUES matter
    for round_i in range(2):
        if transport.is_player_process:
            host_data = {
                "state": rng.standard_normal((rollout_steps, n_envs, 5), dtype=np.float32),
                "actions": np.eye(4, dtype=np.float32)[rng.integers(0, 4, (rollout_steps, n_envs))],
                "logprobs": rng.standard_normal((rollout_steps, n_envs, 1), dtype=np.float32),
                "values": rng.standard_normal((rollout_steps, n_envs, 1), dtype=np.float32),
                "rewards": rng.standard_normal((rollout_steps, n_envs, 1), dtype=np.float32),
                "dones": np.zeros((rollout_steps, n_envs, 1), dtype=np.float32),
            }
            next_values = rng.standard_normal((n_envs, 1), dtype=np.float32)
        else:  # shape/dtype templates only
            host_data = {
                "state": np.zeros((rollout_steps, n_envs, 5), dtype=np.float32),
                "actions": np.zeros((rollout_steps, n_envs, 4), dtype=np.float32),
                "logprobs": np.zeros((rollout_steps, n_envs, 1), dtype=np.float32),
                "values": np.zeros((rollout_steps, n_envs, 1), dtype=np.float32),
                "rewards": np.zeros((rollout_steps, n_envs, 1), dtype=np.float32),
                "dones": np.zeros((rollout_steps, n_envs, 1), dtype=np.float32),
            }
            next_values = np.zeros((n_envs, 1), dtype=np.float32)

        payload = transport.rollout_to_trainers(
            (host_data, next_values, np.asarray(jax.random.PRNGKey(round_i)), np.float32(0.2), np.float32(0.0))
        )
        device_data, dev_next_values, train_key, clip_coef, ent_coef = payload
        params, opt_state, _flat, _metrics = train_fn(
            params,
            opt_state,
            device_data,
            dev_next_values,
            train_key.astype(jnp.uint32),
            clip_coef,
            ent_coef,
            jnp.float32(1.0),  # lr_scale: no sentinel backoff in this drill
        )

    player_params = transport.params_to_player(params)

    params_after = np.concatenate(
        [np.asarray(leaf.addressable_data(0)).ravel() for leaf in jax.tree_util.tree_leaves(params)]
    )
    if transport.is_player_process:
        flat_player = np.concatenate(
            [np.asarray(leaf).ravel() for leaf in jax.tree_util.tree_leaves(player_params)]
        )
        player_matches = bool(np.array_equal(flat_player, params_after))
        player_device = str(jax.tree_util.tree_leaves(player_params)[0].devices())
    else:
        player_matches = player_params is None  # non-player processes hold no player copy
        player_device = None

    print(
        json.dumps(
            {
                "pid": pid,
                "changed": bool(np.abs(params_after - params_before).max() > 0),
                "digest": float(np.abs(params_after).sum()),
                "head": params_after[:5].round(6).tolist(),
                "player_matches": player_matches,
                "player_device": player_device,
            }
        )
    )
    # compile skew on a 1-core host can exceed the distributed shutdown-barrier
    # timeout; leave together
    runtime.barrier()


if __name__ == "__main__":
    main()
