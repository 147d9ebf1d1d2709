"""Child process for the CLI-driven cross-host decoupled tests (test_multihost.py).

Run as: python tests/decoupled_cli_child.py <coordinator_port> <process_id> <num_processes> <tmpdir> [algo]

Unlike decoupled_child.py (which drives the transport primitives by hand), this
child goes through the REAL CLI entrypoint — ``sheeprl_tpu.cli.run`` with
``exp=ppo_decoupled``/``exp=sac_decoupled`` and the multihost fabric flags —
proving the cross-host actor-learner path is reachable exactly the way the
reference's multi-node launch is (``sheeprl exp=ppo_decoupled`` under torchrun,
/root/reference/sheeprl/algos/ppo/ppo_decoupled.py:623-670). jax.distributed is
initialized by the Runtime FROM THE CONFIG, not by this script.

A 2-process world with 2 CPU devices each: global device 0 (process 0) plays,
the other 3 devices form the cross-process trainer mesh. One dry_run iteration
trains end-to-end and writes the final checkpoint on the player process.
Prints one JSON line with the run's observable outcomes.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = [f for f in os.environ.get("XLA_FLAGS", "").split() if "host_platform_device_count" not in f]
flags.append("--xla_force_host_platform_device_count=2")
os.environ["XLA_FLAGS"] = " ".join(flags)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    port, pid, nproc, tmpdir = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
    algo = sys.argv[5] if len(sys.argv) > 5 else "ppo_decoupled"
    if os.environ.get("XH_DEBUG"):  # dump a stack if a collective wedges this process
        import faulthandler

        faulthandler.dump_traceback_later(int(os.environ["XH_DEBUG"]), exit=True, file=sys.stderr)
    os.chdir(tmpdir)

    from sheeprl_tpu.cli import run

    common = [
        "dry_run=True",
        "env=dummy",
        "env.num_envs=3",
        "env.sync_env=True",
        "env.capture_video=False",
        "fabric.accelerator=cpu",
        "fabric.devices=2",
        "fabric.multihost=True",
        f"fabric.coordinator_address=localhost:{port}",
        f"fabric.num_processes={nproc}",
        f"fabric.process_id={pid}",
        "metric.log_level=0",
        "checkpoint.save_last=True",
        "buffer.memmap=False",
        "algo.mlp_keys.encoder=[state]",
        "algo.run_test=False",
    ]
    if algo == "ppo_decoupled":
        args = common + [
            "exp=ppo_decoupled",
            "env.id=discrete_dummy",
            "algo.rollout_steps=4",
            "algo.per_rank_batch_size=4",  # x3 trainer devices = n_data (4 steps x 3 envs)
            "algo.update_epochs=1",
            "algo.cnn_keys.encoder=[]",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
        ]
    else:
        args = common + [
            "exp=sac_decoupled",
            "env.id=continuous_dummy",
            "algo.per_rank_batch_size=2",
            "algo.learning_starts=0",
            "algo.hidden_size=8",
            "buffer.size=64",
        ]
    run(overrides=args)

    ckpts = []
    for root, _, files in os.walk(os.path.join(tmpdir, "logs")):
        ckpts += [os.path.join(root, f) for f in files if f.endswith(".ckpt")]
    print(json.dumps({"pid": pid, "done": True, "n_ckpts": len(ckpts)}))


if __name__ == "__main__":
    main()
