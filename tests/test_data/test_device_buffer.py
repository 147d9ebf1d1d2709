"""DeviceSequentialReplayBuffer: HBM-resident storage/sampling parity checks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.data.device_buffer import DeviceSequentialReplayBuffer


def _step(t, n_envs=2, extra=0.0):
    """A recognizable [1, n_envs, ...] transition: values encode (t, env)."""
    base = np.arange(n_envs, dtype=np.float32)[None, :]
    return {
        "obs": np.full((1, n_envs, 3), t, dtype=np.float32) + base[..., None] * 100 + extra,
        "rewards": np.full((1, n_envs, 1), t, dtype=np.float32),
        "pix": np.full((1, n_envs, 2, 4, 4), t % 256, dtype=np.uint8),
    }


def test_add_and_sample_shapes_on_device():
    rb = DeviceSequentialReplayBuffer(16, n_envs=2)
    rb.seed(0)
    for t in range(8):
        rb.add(_step(t))
    out = rb.sample(batch_size=3, sequence_length=4, n_samples=2)
    assert out["obs"].shape == (2, 4, 3, 3)
    assert out["pix"].shape == (2, 4, 3, 2, 4, 4)
    assert isinstance(out["obs"], jax.Array)
    assert out["pix"].dtype == jnp.uint8


def test_sequences_are_consecutive():
    rb = DeviceSequentialReplayBuffer(32, n_envs=2)
    rb.seed(1)
    for t in range(20):
        rb.add(_step(t))
    out = rb.sample(batch_size=8, sequence_length=5, n_samples=3)
    rew = np.asarray(out["rewards"])  # [G, T, B, 1]
    diffs = np.diff(rew[..., 0], axis=1)
    np.testing.assert_array_equal(diffs, np.ones_like(diffs))


def test_wraparound_never_crosses_write_head():
    rb = DeviceSequentialReplayBuffer(8, n_envs=1)
    rb.seed(2)
    for t in range(20):  # wraps 2.5x
        rb.add(_step(t, n_envs=1))
    out = rb.sample(batch_size=64, sequence_length=3, n_samples=1)
    rew = np.asarray(out["rewards"])[0, :, :, 0]  # [T, B]
    # all sampled values must come from the last 8 steps, consecutive
    assert rew.min() >= 12
    np.testing.assert_array_equal(np.diff(rew, axis=0), np.ones_like(np.diff(rew, axis=0)))


def test_partial_env_add_advances_only_those_envs():
    rb = DeviceSequentialReplayBuffer(16, n_envs=3)
    rb.seed(3)
    for t in range(4):
        rb.add(_step(t, n_envs=3))
    rb.add({k: v[:, :2] for k, v in _step(99, n_envs=3).items()}, indices=[0, 2])
    assert rb._pos.tolist() == [5, 4, 5]
    # env 1's head is untouched; envs 0/2 got the extra row
    buf = {k: np.asarray(jax.device_get(v)) for k, v in rb.buffer.items()}
    assert buf["rewards"][4, 0, 0] == 99
    assert buf["rewards"][4, 2, 0] == 99
    assert buf["rewards"][4, 1, 0] == 0  # untouched slot


def test_too_short_raises():
    rb = DeviceSequentialReplayBuffer(16, n_envs=1)
    rb.add(_step(0, n_envs=1))
    with pytest.raises(ValueError, match="not enough history"):
        rb.sample(batch_size=1, sequence_length=4)


def test_checkpoint_roundtrip():
    rb = DeviceSequentialReplayBuffer(8, n_envs=2)
    rb.seed(4)
    for t in range(11):
        rb.add(_step(t))
    state = rb.state_dict()
    rb2 = DeviceSequentialReplayBuffer(8, n_envs=2)
    rb2.load_state_dict(state)
    rb2.seed(4)
    assert rb2._pos.tolist() == rb._pos.tolist()
    assert rb2.full == rb.full
    a = np.asarray(rb.sample(batch_size=4, sequence_length=3)["obs"])
    b = np.asarray(rb2.sample(batch_size=4, sequence_length=3)["obs"])
    np.testing.assert_array_equal(a, b)


def test_dtype_narrowing_and_uint8_storage():
    rb = DeviceSequentialReplayBuffer(4, n_envs=1)
    rb.add({"a": np.zeros((1, 1, 2), dtype=np.float64), "b": np.zeros((1, 1, 2), dtype=np.int64)})
    assert rb.buffer["a"].dtype == jnp.float32
    assert rb.buffer["b"].dtype == jnp.int32


def test_later_add_with_different_dtype_is_coerced_not_bitcast():
    """A leaf arriving with a dtype that differs from the allocation-time storage
    dtype must be VALUE-cast before packing: the packed byte stream is decoded
    with the storage dtype, so a same-itemsize mismatch (int32 vs float32) would
    otherwise silently reinterpret bits, and a different itemsize would misalign
    every later leaf in the stream."""
    rb = DeviceSequentialReplayBuffer(8, n_envs=1)
    rb.add({"r": np.full((1, 1, 1), 1.0, dtype=np.float32), "z": np.zeros((1, 1, 2), np.float32)})
    # same itemsize, different kind: int32 values 7 must land as float32 7.0
    rb.add({"r": np.full((1, 1, 1), 7, dtype=np.int32), "z": np.ones((1, 1, 2), np.float32)})
    # different itemsize: float16 3.0 must not shift the byte offsets of 'z'
    rb.add({"r": np.full((1, 1, 1), 3.0, dtype=np.float16), "z": np.full((1, 1, 2), 5.0, np.float32)})
    buf = {k: np.asarray(jax.device_get(v)) for k, v in rb.buffer.items()}
    np.testing.assert_array_equal(buf["r"][:3, 0, 0], [1.0, 7.0, 3.0])
    np.testing.assert_array_equal(buf["z"][2, 0, :2], [5.0, 5.0])


def test_dv3_cli_with_device_buffer(tmp_path, monkeypatch):
    """End-to-end DV3 smoke over the HBM-resident buffer path."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu.cli import run

    run(
        overrides=[
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            "env.num_envs=2",
            "env.sync_env=True",
            "env.capture_video=False",
            "dry_run=True",
            "metric.log_level=0",
            "checkpoint.save_last=False",
            "fabric.devices=1",
            "buffer.device=True",
            "algo.learning_starts=0",
            "algo.per_rank_sequence_length=1",
            "algo.per_rank_batch_size=2",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=8",
            "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.transition_model.hidden_size=8",
            "algo.world_model.discrete_size=2",
            "algo.world_model.stochastic_size=2",
            "algo.horizon=3",
        ]
    )


def test_dv1_cli_with_device_buffer(tmp_path, monkeypatch):
    """DV1's sequential path supports the HBM-resident buffer too (its
    pixel-target recipe defaults to it: no bulk host->device batch transfers)."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu.cli import run

    run(
        overrides=[
            "exp=dreamer_v1",
            "env=dummy",
            "env.id=discrete_dummy",
            "env.num_envs=2",
            "env.sync_env=True",
            "env.capture_video=False",
            "dry_run=True",
            "metric.log_level=0",
            "checkpoint.save_last=False",
            "fabric.devices=1",
            "buffer.device=True",
            "algo.learning_starts=0",
            "algo.per_rank_sequence_length=1",
            "algo.per_rank_batch_size=2",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=8",
            "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.transition_model.hidden_size=8",
            "algo.world_model.stochastic_size=2",
            "algo.horizon=3",
        ]
    )


def test_dv2_cli_with_device_buffer(tmp_path, monkeypatch):
    """DV2's sequential path supports the HBM-resident buffer too."""
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu.cli import run

    run(
        overrides=[
            "exp=dreamer_v2",
            "env=dummy",
            "env.id=discrete_dummy",
            "env.num_envs=2",
            "env.sync_env=True",
            "env.capture_video=False",
            "dry_run=True",
            "metric.log_level=0",
            "checkpoint.save_last=False",
            "fabric.devices=1",
            "buffer.device=True",
            "algo.learning_starts=0",
            "algo.per_rank_sequence_length=1",
            "algo.per_rank_batch_size=2",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            "algo.world_model.recurrent_model.recurrent_state_size=8",
            "algo.world_model.representation_model.hidden_size=8",
            "algo.world_model.transition_model.hidden_size=8",
            "algo.world_model.discrete_size=2",
            "algo.world_model.stochastic_size=2",
            "algo.horizon=3",
        ]
    )


def test_episode_buffer_rejects_device(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    from sheeprl_tpu.cli import run

    with pytest.raises(ValueError, match="sequential replay only"):
        run(
            overrides=[
                "exp=dreamer_v2",
                "env=dummy",
                "env.id=discrete_dummy",
                "env.num_envs=2",
                "env.sync_env=True",
                "env.capture_video=False",
                "dry_run=True",
                "metric.log_level=0",
                "checkpoint.save_last=False",
                "fabric.devices=1",
                "buffer.device=True",
                "buffer.type=episode",
            ]
        )
