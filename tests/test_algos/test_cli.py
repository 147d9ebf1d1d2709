"""CLI-level tests: shim invocation, resume round-trip, resume mismatch errors,
evaluation from checkpoint (reference tests/test_algos/test_cli.py:99-277)."""

import os
import subprocess
import sys

import pytest

from sheeprl_tpu.cli import evaluation, run

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_PPO = [
    "exp=ppo",
    "env=dummy",
    "env.id=discrete_dummy",
    "env.num_envs=1",
    "env.sync_env=True",
    "env.capture_video=False",
    "fabric.devices=1",
    "metric.log_level=0",
    "algo.rollout_steps=4",
    "algo.per_rank_batch_size=2",
    "algo.update_epochs=1",
    "algo.total_steps=16",
    "algo.mlp_keys.encoder=[state]",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.run_test=False",
    "buffer.memmap=False",
]


def _find_ckpts(root):
    found = []
    for base, _, files in os.walk(root):
        found += [os.path.join(base, f) for f in files if f.endswith(".ckpt")]
    return sorted(found)


def test_run_algo_subprocess(tmp_path):
    """The `python sheeprl.py ...` shim end-to-end in a fresh interpreter."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "sheeprl.py"), *TINY_PPO, "dry_run=True", "checkpoint.save_last=False"],
        cwd=tmp_path,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]


def test_resume_from_checkpoint(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(overrides=TINY_PPO + ["checkpoint.save_last=True"])
    ckpts = _find_ckpts(tmp_path / "logs")
    assert ckpts, "training did not write a checkpoint"
    run(overrides=TINY_PPO + ["checkpoint.save_last=False", f"checkpoint.resume_from={ckpts[-1]}"])


def test_resume_from_checkpoint_decoupled(tmp_path, monkeypatch):
    """Decoupled PPO writes its checkpoint from the player role with the
    trainer-world batch accounting; a resume must rebuild both roles from it
    (reference resumes decoupled runs through the same cli path)."""
    monkeypatch.chdir(tmp_path)
    tiny = [
        "exp=ppo_decoupled",
        "env=dummy",
        "env.id=discrete_dummy",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "fabric.devices=3",
        "metric.log_level=0",
        "algo.rollout_steps=4",
        "algo.per_rank_batch_size=2",
        "algo.update_epochs=1",
        "algo.total_steps=16",
        "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.encoder=[]",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.run_test=False",
        "buffer.memmap=False",
    ]
    # checkpoint MID-run (not save_last): the resume leg must actually train
    # from the restored state, not just load it and exit
    run(overrides=tiny + ["checkpoint.save_last=False", "checkpoint.every=8"])
    ckpts = _find_ckpts(tmp_path / "logs")
    assert ckpts, "decoupled training did not write a checkpoint"
    run(overrides=tiny + ["checkpoint.save_last=False", f"checkpoint.resume_from={ckpts[0]}"])


def test_resume_from_checkpoint_sac_decoupled(tmp_path, monkeypatch):
    """SAC decoupled checkpoints carry the replay-ratio scheduler and update
    counter alongside the params; resume must rehydrate all of it."""
    monkeypatch.chdir(tmp_path)
    tiny = [
        "exp=sac_decoupled",
        "env=dummy",
        "env.id=continuous_dummy",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "fabric.devices=2",
        "metric.log_level=0",
        "algo.per_rank_batch_size=2",
        "algo.learning_starts=0",
        "algo.total_steps=8",
        "algo.hidden_size=8",
        "algo.mlp_keys.encoder=[state]",
        "algo.run_test=False",
        "buffer.memmap=False",
        "buffer.size=64",
    ]
    # checkpoint MID-run (not save_last) so the resume leg trains from the
    # restored scheduler/optimizer state instead of loading and exiting
    run(overrides=tiny + ["checkpoint.save_last=False", "checkpoint.every=4"])
    ckpts = _find_ckpts(tmp_path / "logs")
    assert ckpts, "decoupled SAC training did not write a checkpoint"
    run(overrides=tiny + ["checkpoint.save_last=False", f"checkpoint.resume_from={ckpts[0]}"])


def test_resume_from_checkpoint_env_error(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(overrides=TINY_PPO + ["checkpoint.save_last=True"])
    ckpts = _find_ckpts(tmp_path / "logs")
    args = [a if not a.startswith("env.id=") else "env.id=continuous_dummy" for a in TINY_PPO]
    with pytest.raises(ValueError, match="different environment"):
        run(overrides=args + [f"checkpoint.resume_from={ckpts[-1]}"])


def test_resume_from_checkpoint_algo_error(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(overrides=TINY_PPO + ["checkpoint.save_last=True"])
    ckpts = _find_ckpts(tmp_path / "logs")
    args = [a if a != "exp=ppo" else "exp=a2c" for a in TINY_PPO]
    with pytest.raises(ValueError, match="different algorithm"):
        run(overrides=args + [f"checkpoint.resume_from={ckpts[-1]}"])


def test_evaluate(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    run(overrides=TINY_PPO + ["checkpoint.save_last=True", "dry_run=True"])
    ckpts = _find_ckpts(tmp_path / "logs")
    assert ckpts
    evaluation(overrides=[f"checkpoint_path={ckpts[-1]}", "env.capture_video=False"])


TINY_DV3 = [
    "exp=dreamer_v3",
    "env=dummy",
    "env.id=discrete_dummy",
    "env.num_envs=1",
    "env.sync_env=True",
    "env.capture_video=False",
    "fabric.devices=1",
    "metric.log_level=0",
    "algo.per_rank_batch_size=1",
    "algo.per_rank_sequence_length=2",
    "buffer.size=16",
    "algo.learning_starts=4",
    "algo.total_steps=8",
    "algo.replay_ratio=1",
    "algo.horizon=4",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[state]",
    "algo.run_test=False",
    "buffer.memmap=False",
]


def test_resume_and_evaluate_dreamer_v3(tmp_path, monkeypatch):
    """Checkpoint round-trip + eval-from-checkpoint for the flagship world model.

    The first run checkpoints MID-run (checkpoint.every=4 < total_steps=8), so
    the resume leg really trains iterations 5..8 with the restored optimizer /
    Moments / Ratio state (resume keeps the sidecar config's total_steps: CLI
    overrides other than checkpoint/seed/fabric are deliberately dropped on
    resume, reference cli.py:23-57)."""
    monkeypatch.chdir(tmp_path)
    run(overrides=TINY_DV3 + ["checkpoint.save_last=True", "checkpoint.every=4"])
    ckpts = _find_ckpts(tmp_path / "logs")
    assert ckpts, "DV3 training did not write a checkpoint"
    mid_ckpt = next(c for c in ckpts if "ckpt_4_" in os.path.basename(c))
    run(overrides=TINY_DV3 + ["checkpoint.save_last=False", f"checkpoint.resume_from={mid_ckpt}"])
    evaluation(overrides=[f"checkpoint_path={ckpts[-1]}", "env.capture_video=False"])


def test_evaluate_requires_checkpoint_path():
    from sheeprl_tpu.config import ConfigError

    with pytest.raises(ConfigError, match="checkpoint_path"):
        evaluation(overrides=[])


def test_decoupled_requires_two_devices(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    with pytest.raises(RuntimeError, match="at least 2 devices"):
        run(
            overrides=[
                "exp=ppo_decoupled",
                "env=dummy",
                "env.id=discrete_dummy",
                "env.capture_video=False",
                "fabric.devices=1",
                "metric.log_level=0",
                "algo.mlp_keys.encoder=[state]",
                "dry_run=True",
            ]
        )


def test_env_platforms_alone_selects_the_backend(tmp_path):
    """``JAX_PLATFORMS=cpu`` in the environment is enough to hold a child to
    the CPU: importing the CLI needs no in-code ``jax_platforms`` update (every
    CPU drill and launcher child relies on exactly that)."""
    out = subprocess.run(
        [
            sys.executable,
            "-c",
            "import sheeprl_tpu.cli, jax; "
            "assert jax.config.jax_platforms == 'cpu', jax.config.jax_platforms; "
            "print(jax.devices()[0].platform)",
        ],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("cpu")
