"""Shared replay/rollout-buffer + sampling-pipeline construction for the train loops.

One place decides between the host path (EnvIndependentReplayBuffer over
SequentialReplayBuffer + the double-buffered DevicePrefetcher) and the
HBM-resident path (``buffer.backend=device`` -> DeviceSequentialReplayBuffer +
InlineSampler), so the seven sequential-replay train loops cannot drift apart.
The on-policy family (PPO/A2C) goes through :func:`make_rollout_buffer`, which
maps the same ``buffer.backend`` switch onto the host numpy ``ReplayBuffer``
vs the HBM-resident ``DeviceRolloutBuffer``.
"""

from __future__ import annotations

import os
import warnings
from typing import Any, Optional, Sequence, Tuple

from jax.sharding import NamedSharding, PartitionSpec as P

from sheeprl_tpu.core.compile import setup_phase
from sheeprl_tpu.data.buffers import (
    EnvIndependentReplayBuffer,
    EpisodeBuffer,
    ReplayBuffer,
    SequentialReplayBuffer,
)
from sheeprl_tpu.data.device_buffer import DeviceSequentialReplayBuffer, ShardedDeviceSequentialReplayBuffer
from sheeprl_tpu.data.prefetch import DevicePrefetcher, InlineSampler
from sheeprl_tpu.data.rollout_buffer import DeviceRolloutBuffer

__all__ = [
    "buffer_backend",
    "make_episode_replay",
    "make_replay_ring",
    "make_rollout_buffer",
    "make_sequential_replay",
]


def buffer_backend(cfg) -> str:
    """The resolved ``buffer.backend`` ("host" | "device").

    ``buffer.device=True`` (the pre-backend switch for the off-policy HBM
    replay) is accepted as an alias of ``backend=device`` so existing override
    lines keep working; either switch alone selects the device path (the
    config default for both is host).
    """
    backend = str(cfg.buffer.get("backend", "host") or "host").lower()
    if backend not in ("host", "device"):
        raise ValueError(f"buffer.backend must be 'host' or 'device'; got {backend!r}")
    if bool(cfg.buffer.get("device", False)):
        return "device"
    return backend


def make_rollout_buffer(cfg, runtime, n_envs: int, obs_keys: Sequence[str], log_dir: Optional[str]):
    """The on-policy rollout store for the PPO/A2C family.

    - ``buffer.backend=host`` (default): the reference design — a circular numpy
      ``ReplayBuffer`` of ``cfg.buffer.size`` rows, optionally memmapped; every
      step's policy outputs are pulled to host and the whole ``[T, B]`` rollout
      is re-uploaded each iteration.
    - ``buffer.backend=device``: a ``DeviceRolloutBuffer`` of exactly
      ``cfg.algo.rollout_steps`` rows resident on ``runtime.player_device``;
      policy outputs are scattered in-graph, env products ride one packed
      ``device_put`` per step, and the iteration handoff is device->device.
      ``buffer.size > rollout_steps`` keeps extra history host-side only, which
      the device layout doesn't model — use the host backend for that.
    """
    env_cfg = getattr(cfg, "env", None)
    if env_cfg is not None and str(env_cfg.get("backend", "gym")).lower() == "ingraph":
        # the fused in-graph collector (envs/ingraph/rollout.py) materializes
        # the [T, B] rollout directly in the buffer layout as its scan output —
        # there is no incremental store to manage. The vmapped population loop
        # (envs/ingraph/population.py) stacks the same layout to [N, T, B] per
        # member inside one compiled epoch, so it too runs bufferless.
        return None
    if buffer_backend(cfg) == "device":
        if cfg.buffer.get("memmap", False):
            # memmap defaults True for the host path; flipping backend=device
            # alone must work, so this is advisory (same as the off-policy
            # device replay, which has no host storage to memmap either)
            warnings.warn("buffer.memmap has no effect with buffer.backend=device (storage lives in HBM)")
        if int(cfg.buffer.size) > int(cfg.algo.rollout_steps):
            raise ValueError(
                f"buffer.backend=device stores exactly one rollout ({cfg.algo.rollout_steps} steps); "
                f"buffer.size={cfg.buffer.size} rows of retained history need buffer.backend=host"
            )
        return DeviceRolloutBuffer(
            int(cfg.algo.rollout_steps), n_envs, device=runtime.player_device
        )
    return ReplayBuffer(
        cfg.buffer.size,
        n_envs,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir or ".", "memmap_buffer", f"rank_{runtime.global_rank}"),
        obs_keys=tuple(obs_keys),
    )


def make_replay_ring(cfg, n_envs: int, leaf_specs):
    """The HBM transition store for the fused off-policy in-graph path (SAC).

    Keyed off ``env.backend`` the same way :func:`make_rollout_buffer` is for
    the on-policy family: only the ingraph backend keeps transitions in-graph
    (a :class:`~sheeprl_tpu.envs.ingraph.replay_ring.ReplayRing` written and
    sampled inside the fused iteration); the gym backend keeps the host
    ``ReplayBuffer``. Capacity follows the host convention — ``buffer.size``
    transitions total, i.e. ``buffer.size // n_envs`` ring rows of ``n_envs``
    transitions each. The ring is never memmapped or checkpointed (it is a
    donated device pytree; resume re-warms it from the env).
    """
    env_cfg = getattr(cfg, "env", None)
    backend = str(env_cfg.get("backend", "gym")).lower() if env_cfg is not None else "gym"
    if backend != "ingraph":
        raise ValueError(
            "make_replay_ring builds the env.backend=ingraph transition store; "
            f"the '{backend}' backend uses the host ReplayBuffer"
        )
    from sheeprl_tpu.envs.ingraph.replay_ring import ReplayRing

    capacity = max(int(cfg.buffer.size) // int(n_envs), 1) if not cfg.dry_run else 2
    return ReplayRing(capacity, int(n_envs), leaf_specs)


@setup_phase("replay")
def make_sequential_replay(
    cfg,
    runtime,
    log_dir: Optional[str],
    obs_keys: Sequence[str] = (),
) -> Tuple[Any, Any]:
    """Return ``(rb, prefetcher)`` for a sequential-replay loop.

    - host path: per-env circular numpy/memmap buffers; a worker thread overlaps
      sample + async device_put with the previous train step (see
      sheeprl_tpu/data/prefetch.py); batches land sharded [G, T, B] on the mesh;
    - ``cfg.buffer.device=True``: storage and sampling live in HBM
      (sheeprl_tpu/data/device_buffer.py) and the "prefetcher" is a passthrough.

    Train loops use the pair uniformly: ``prefetcher.get(...)`` for batches,
    ``with prefetcher.guard(): rb.add(...)`` for writes, ``rb.patch_last(...)``
    for crash-restart boundary patches, ``prefetcher.close()`` at teardown.
    """
    buffer_size = (
        cfg.buffer.size // int(cfg.env.num_envs * runtime.world_size) if not cfg.dry_run else 2
    )
    use_device_buffer = buffer_backend(cfg) == "device"
    if use_device_buffer:
        if runtime.world_size > 1:
            import jax

            if jax.process_count() > 1:
                # the sharded buffer's writes/gathers assume every mesh device is
                # addressable from this controller; per-process env data against a
                # global-mesh sharding would silently drop foreign columns
                raise ValueError(
                    "buffer.backend=device is single-controller only (one process, any "
                    "number of local devices); use the host buffer for multihost runs"
                )
            # env axis mapped onto the mesh's data axis: local writes/gathers,
            # batches come out already [G, T, B]-sharded for the train step
            rb = ShardedDeviceSequentialReplayBuffer(
                buffer_size, n_envs=cfg.env.num_envs, mesh=runtime.mesh
            )
        else:
            rb = DeviceSequentialReplayBuffer(
                buffer_size, n_envs=cfg.env.num_envs, device=runtime.device
            )
        prefetcher = InlineSampler(rb.sample)
    else:
        rb = EnvIndependentReplayBuffer(
            buffer_size,
            n_envs=cfg.env.num_envs,
            obs_keys=tuple(obs_keys),
            memmap=cfg.buffer.memmap,
            memmap_dir=os.path.join(log_dir or ".", "memmap_buffer", f"rank_{runtime.global_rank}"),
            buffer_cls=SequentialReplayBuffer,
        )
        prefetcher = DevicePrefetcher(
            rb.sample,
            device=NamedSharding(runtime.mesh, P(None, None, "data")),
            chunk=int(cfg.buffer.get("prefetch_batches", 1)),
            chunk_key="n_samples",
        )
    return rb, prefetcher


def make_episode_replay(
    cfg,
    runtime,
    log_dir: Optional[str],
    obs_keys: Sequence[str] = (),
) -> Tuple[Any, Any]:
    """Return ``(rb, prefetcher)`` for the episode-layout loops (DV2 family).

    Episode buffers keep whole trajectories host-side (variable-length episodes
    don't map onto the fixed-slot HBM layout), so ``buffer.device=True`` raises
    and the pipeline is always the double-buffered host prefetcher.
    """
    if buffer_backend(cfg) == "device":
        raise ValueError(
            "buffer.backend=device supports sequential replay only; "
            "buffer.type=episode must use the host buffer"
        )
    buffer_size = (
        cfg.buffer.size // int(cfg.env.num_envs * runtime.world_size) if not cfg.dry_run else 2
    )
    rb = EpisodeBuffer(
        buffer_size,
        minimum_episode_length=1 if cfg.dry_run else cfg.algo.per_rank_sequence_length,
        n_envs=cfg.env.num_envs,
        obs_keys=tuple(obs_keys),
        prioritize_ends=cfg.buffer.prioritize_ends,
        memmap=cfg.buffer.memmap,
        memmap_dir=os.path.join(log_dir or ".", "memmap_buffer", f"rank_{runtime.global_rank}"),
    )
    prefetcher = DevicePrefetcher(
        rb.sample,
        device=NamedSharding(runtime.mesh, P(None, None, "data")),
        chunk=int(cfg.buffer.get("prefetch_batches", 1)),
        chunk_key="n_samples",
    )
    return rb, prefetcher
