"""HBM-resident sequential replay buffer: storage, writes, and sampling on device.

TPU-first alternative to the host-numpy ``EnvIndependentReplayBuffer`` over
``SequentialReplayBuffer`` (reference sheeprl/data/buffers.py:363-527, 529-744
keeps storage host-side and ships every sampled batch over PCIe). Off-policy
pixel workloads at the reference's scale (e.g. DreamerV3 Atari-100K: 100k
frames x 64x64x3 uint8 ~= 1.2 GB) fit comfortably in a single chip's HBM, so
the whole replay pipeline can live on device:

- storage: per-leaf jax arrays in a TILE-AWARE physical layout (see below);
- add: one donated jitted scatter per step — in-place in HBM, the only
  host->device traffic is the new transition itself (~100 KB/step for 8 pixel
  envs, vs ~25 MB/train-iteration for host-sampled [G,T,B] batches);
- sample: host draws the (tiny, int32) start/env indices from per-env valid
  ranges, a jitted gather assembles the ``[G, T, B, *]`` batch entirely in HBM —
  the training step consumes it with ZERO bulk host->device transfer.

Physical layout. TPU HBM buffers are tiled over the last two axes (f32 8x128,
bf16 16x128, uint8 32x128), so the naive logical layout ``[cap, n_envs, *leaf]``
pads catastrophically: ``[cap, 4, 3, 64, 64]`` uint8 doubles (64 -> 128 lanes)
and a ``[cap, 4, 1]`` f32 flag pads 4 -> 8 sublanes x 1 -> 128 lanes = 256x
(0.5 GB for a 2 MB array; a DMC-scale buffer "grew" from 6.3 GB logical to
17.2 GB physical and OOM'd the chip). Each leaf therefore stores as either

- ``chunk`` (feature size F >= one tile quantum): ``[cap, n_envs, P/128, 128]``
  with F padded up to the dtype's tile quantum P (u8: 4096, bf16: 2048, f32:
  1024) — zero padding for 64x64x3 pixels (12288 = 3 u8 quanta); or
- ``tminor`` (small F): ``[n_envs*F, cap]`` — time is the minor axis, so the
  array is lane-dense for any F, per-step writes are tiny pointwise scatters,
  and sequence gathers read stride-1 runs.

Checkpoints store the LOGICAL ``[cap, n_envs, *leaf]`` arrays, so the physical
layout can evolve without breaking resume.

Each env has its OWN circular write head (mirroring EnvIndependentReplayBuffer):
episode-boundary patch rows (``add(reset_data, dones_idxes)``) advance only the
done envs, so per-env histories stay internally contiguous.

Besides bandwidth, this removes the bulk host->device transfers (and whatever
staging memory the transport holds for them) from the training loop entirely.

Interface-compatible with the ``rb.add(data, [env_idxes])`` /
``rb.sample(batch_size, sequence_length=..., n_samples=...)`` calls the Dreamer
train loops make, so ``buffer.device=True`` swaps it in transparently.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["DeviceSequentialReplayBuffer", "ShardedDeviceSequentialReplayBuffer"]


def _shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check disabled (the buffer bodies
    are purely shard-local scatters/gathers; the check only costs trace time
    and rejects the tminor layout's mixed-rank outputs)."""
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


class _LeafMeta(NamedTuple):
    feat: Tuple[int, ...]  # logical per-step feature shape (leaf.shape[2:])
    flat: int  # prod(feat)
    padded: int  # chunk layout: flat padded to the tile quantum; tminor: == flat
    layout: str  # "chunk" | "tminor"
    dtype: Any


def _tile_quantum(dtype) -> int:
    """Smallest feature size that tiles with zero waste: 128 lanes x the dtype's
    sublane count (f32 8, bf16 16, u8 32 -> 1024/2048/4096 elements)."""
    return 128 * max(256 // (np.dtype(dtype).itemsize * 8), 1)


def _leaf_meta(feat: Tuple[int, ...], dtype) -> _LeafMeta:
    flat = int(np.prod(feat)) if feat else 1
    q = _tile_quantum(dtype)
    if flat >= q:
        padded = ((flat + q - 1) // q) * q
        return _LeafMeta(feat, flat, padded, "chunk", dtype)
    return _LeafMeta(feat, flat, flat, "tminor", dtype)


class DeviceSequentialReplayBuffer:
    """Circular per-env replay living in accelerator memory (logical
    ``[capacity, n_envs, *leaf]``; tile-aware physical layout, module docstring)."""

    def __init__(
        self,
        buffer_size: int,
        n_envs: int = 1,
        device: Optional[Any] = None,
    ):
        if buffer_size <= 0:
            raise ValueError(f"a replay buffer needs a positive capacity; received buffer_size={buffer_size}")
        self._buffer_size = int(buffer_size)
        self._n_envs = int(n_envs)
        self._device = device
        self._buf: Optional[Dict[str, jax.Array]] = None
        self._meta: Dict[str, _LeafMeta] = {}
        # independent circular write head per env (host-side bookkeeping)
        self._pos = np.zeros(self._n_envs, dtype=np.int64)
        self._full = np.zeros(self._n_envs, dtype=bool)
        self._rng: np.random.Generator = np.random.default_rng()
        # jit caches: writes keyed by (rows, n_envs_written, keys), gathers by
        # (seq_len, n, keys) — each shape/key-set combination compiles once
        self._write_fns: Dict[Any, Any] = {}
        self._gather_fns: Dict[Any, Any] = {}
        self._view_fns: Dict[Any, Any] = {}

    # ----- properties mirroring the host buffers ---------------------------------------
    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def full(self) -> bool:
        return bool(self._full.all())

    @property
    def is_memmap(self) -> bool:
        return False

    @property
    def buffer(self) -> Optional[Dict[str, jax.Array]]:
        """Materialized LOGICAL ``[cap, n_envs, *leaf]`` view (debug/inspection;
        the hot paths never build it)."""
        if self._buf is None:
            return None
        return {k: self._logical_view(k) for k in self._buf}

    def __len__(self) -> int:
        return self._buffer_size

    def seed(self, seed: Optional[int] = None) -> None:
        self._rng = np.random.default_rng(seed)

    def _filled(self) -> np.ndarray:
        return np.where(self._full, self._buffer_size, self._pos)

    # ----- layout helpers --------------------------------------------------------------
    @staticmethod
    def _narrow(arr: np.ndarray) -> np.ndarray:
        if arr.dtype == np.float64:
            return arr.astype(np.float32)
        if arr.dtype == np.int64:
            return arr.astype(np.int32)
        return arr

    def _to_physical(self, key: str, block: np.ndarray) -> np.ndarray:
        """Host-side: ``[rows, k, *feat]`` -> the physical write-block layout
        (chunk: ``[rows, k, P/128, 128]``; tminor: ``[k, F, rows]``)."""
        m = self._meta[key]
        rows, k = block.shape[:2]
        flat = np.ascontiguousarray(block).reshape(rows, k, m.flat)
        if m.layout == "chunk":
            if m.padded != m.flat:
                pad = np.zeros((rows, k, m.padded - m.flat), dtype=flat.dtype)
                flat = np.concatenate([flat, pad], axis=-1)
            return flat.reshape(rows, k, m.padded // 128, 128)
        return np.ascontiguousarray(flat.transpose(1, 2, 0))  # [k, F, rows]

    def _storage_shape(self, key: str) -> Tuple[int, ...]:
        m = self._meta[key]
        if m.layout == "chunk":
            return (self._buffer_size, self._n_envs, m.padded // 128, 128)
        return (self._n_envs * m.flat, self._buffer_size)

    def _view_closure(self, key: str):
        """Physical -> logical [cap, n_envs, *feat] reconstruction; pure reshape/
        slice/transpose math, valid on device (jit) and host (numpy) alike."""
        m = self._meta[key]
        cap, envs = self._buffer_size, self._n_envs

        def view(store):
            if m.layout == "chunk":
                out = store.reshape(cap, envs, m.padded)[..., : m.flat]
            else:
                out = store.reshape(envs, m.flat, cap).transpose(2, 0, 1)
            return out.reshape(cap, envs, *m.feat)

        return view

    def _logical_view(self, key: str) -> jax.Array:
        if key not in self._view_fns:
            self._view_fns[key] = jax.jit(self._view_closure(key))
        return self._view_fns[key](self._buf[key])

    def _logical_to_host(self, key: str) -> np.ndarray:
        """Checkpoint path: de-layout HOST-side so no second logical-size HBM
        allocation forms next to the physical storage (the jitted view would
        transiently double the buffer's footprint on device)."""
        return np.ascontiguousarray(self._view_closure(key)(np.asarray(jax.device_get(self._buf[key]))))

    # ----- write path ------------------------------------------------------------------
    def _put(self, v: np.ndarray) -> jax.Array:
        return jax.device_put(v, self._device)

    def _allocate(self, data: Dict[str, np.ndarray]) -> None:
        buf = {}
        for k, v in data.items():
            leaf = self._narrow(np.asarray(v))
            self._meta[k] = _leaf_meta(tuple(leaf.shape[2:]), leaf.dtype)
            buf[k] = jax.jit(
                partial(jnp.zeros, self._storage_shape(k), leaf.dtype),
                out_shardings=None if self._device is None else jax.sharding.SingleDeviceSharding(self._device),
            )()
        self._buf = buf

    def _phys_block_shape(self, key: str, rows: int, k: int) -> Tuple[int, ...]:
        m = self._meta[key]
        if m.layout == "chunk":
            return (rows, k, m.padded // 128, 128)
        return (k, m.flat, rows)

    def _pack(self, data: Dict[str, np.ndarray], pos: np.ndarray, env_idx: np.ndarray) -> np.ndarray:
        """Serialize one write (indices + every leaf's physical block) into a single
        byte buffer: every device_put carries a fixed cost, so the 8-put add
        becomes ONE transfer, unpacked in-graph."""
        parts = [pos.astype("<i4").tobytes(), env_idx.astype("<i4").tobytes()]
        for key in sorted(data):
            leaf = self._narrow(np.asarray(data[key]))
            store_dtype = self._meta[key].dtype
            if leaf.dtype != store_dtype:
                # The packed byte stream is decoded with the storage dtype captured at
                # allocation; a leaf arriving with a different (same-itemsize) dtype
                # would be bit-reinterpreted and a different itemsize would misalign
                # every later leaf in the stream. Coerce here, exactly as the pre-pack
                # write path did in-graph via astype(store.dtype).
                leaf = leaf.astype(store_dtype)
            parts.append(np.ascontiguousarray(self._to_physical(key, leaf)).tobytes())
        return np.frombuffer(b"".join(parts), np.uint8)

    def _write_fn(self, rows: int, k: int, keys_sig):
        """Donated writer: ONE packed uint8 buffer in, blocks land at per-env heads."""
        cache_key = (rows, k, keys_sig)
        if cache_key not in self._write_fns:
            cap = self._buffer_size
            metas = {key: self._meta[key] for key in keys_sig}
            shapes = {key: self._phys_block_shape(key, rows, k) for key in keys_sig}

            def write(buf, packed):
                off = 0

                def take(nbytes):
                    nonlocal off
                    seg = jax.lax.slice(packed, (off,), (off + nbytes,))
                    off += nbytes
                    return seg

                def decode(nelem, dtype, shape):
                    it = np.dtype(dtype).itemsize
                    raw = take(nelem * it)
                    if it == 1:
                        return jax.lax.bitcast_convert_type(raw, dtype).reshape(shape)
                    return jax.lax.bitcast_convert_type(raw.reshape(-1, it), dtype).reshape(shape)

                pos = decode(k, jnp.int32, (k,))
                env_idx = decode(k, jnp.int32, (k,))
                blocks = {
                    key: decode(int(np.prod(shapes[key])), metas[key].dtype, shapes[key])
                    for key in keys_sig
                }
                row_idx = (pos[None, :] + jnp.arange(rows)[:, None]) % cap  # [rows, k]

                def one(key, store, new):
                    m = metas[key]
                    if m.layout == "chunk":
                        # new: [rows, k, C, 128]
                        return store.at[row_idx, env_idx[None, :]].set(new.astype(store.dtype))
                    # new: [k, F, rows]; rowsel [k, F]; cols [k, rows]
                    rowsel = env_idx[:, None] * m.flat + jnp.arange(m.flat)[None, :]
                    cols = (pos[:, None] + jnp.arange(rows)[None, :]) % cap
                    return store.at[rowsel[:, :, None], cols[:, None, :]].set(new.astype(store.dtype))

                return {key: one(key, buf[key], blocks[key]) for key in buf}

            self._write_fns[cache_key] = jax.jit(write, donate_argnums=(0,))
        return self._write_fns[cache_key]

    def add(
        self,
        data: Dict[str, np.ndarray],
        indices: Optional[Sequence[int]] = None,
        validate_args: bool = False,
    ) -> None:
        """Append a ``[T, n_envs or len(indices), ...]`` block at each env's head."""
        if validate_args:
            from sheeprl_tpu.data.buffers import _validate_added_data

            _validate_added_data(data)
        first = next(iter(data.values()))
        rows = int(np.asarray(first).shape[0])
        if self._buf is None:
            if indices is not None:
                raise RuntimeError("The first add must cover every env (no partial-env add into an empty buffer)")
            self._allocate(data)
        env_idx = (
            np.arange(self._n_envs, dtype=np.int64)
            if indices is None
            else np.asarray(list(indices), dtype=np.int64)
        )
        pos = self._pos[env_idx]
        self._buf = self._write_fn(rows, len(env_idx), tuple(sorted(data)))(
            self._buf, self._put(self._pack(data, pos, env_idx))
        )
        new_pos = pos + rows
        self._full[env_idx] |= new_pos >= self._buffer_size
        self._pos[env_idx] = new_pos % self._buffer_size

    def _write_rows(self, values: Dict[str, np.ndarray], env_idx: np.ndarray, pos: np.ndarray) -> None:
        """Overwrite one row of the given envs with host values ``[k, *feat]``."""
        keys_sig = tuple(sorted(values))
        sub = {k: self._buf[k] for k in keys_sig}
        rows_data = {k: np.asarray(v)[None] for k, v in values.items()}
        out = self._write_fn(1, len(env_idx), keys_sig)(
            sub, self._put(self._pack(rows_data, pos, env_idx))
        )
        self._buf.update(out)

    def _read_row(self, key: str, env_idx: np.ndarray, pos: np.ndarray) -> np.ndarray:
        """Host copy of one row per env: ``[k, *feat]`` (tiny; checkpoint/patch path)."""
        out = self._gather((key,), 1, len(env_idx))(
            {key: self._buf[key]}, self._put(np.stack([pos, env_idx]).astype(np.int32))
        )[key]
        return np.asarray(jax.device_get(out))[:, 0]  # [k, T=1, *feat] -> [k, *feat]

    def _patch_truncated(self):
        """Force the last written step of every env to 'truncated'; return undo state.

        Checkpoint-time episode-boundary patching (same contract as the host
        ReplayBuffer._patch_truncated): sequences sampled after a resume must not
        bootstrap across the save/restart discontinuity.
        """
        if self._buf is None or "truncated" not in self._buf:
            return None
        env_idx = np.arange(self._n_envs, dtype=np.int64)
        last = ((self._pos - 1) % self._buffer_size).astype(np.int64)
        terminated = self._read_row("terminated", env_idx, last)
        original = self._read_row("truncated", env_idx, last)
        patched = np.where(terminated > 0, 0, 1).astype(original.dtype)
        self._write_rows({"truncated": patched}, env_idx, last)
        return (last, original)

    def _unpatch_truncated(self, undo) -> None:
        if undo is None:
            return
        last, original = undo
        self._write_rows({"truncated": original}, np.arange(self._n_envs, dtype=np.int64), last)

    def patch_last(self, env_indices: Sequence[int], values: Dict[str, float]) -> None:
        """Overwrite scalar keys of the most recent row of the given envs.

        The RestartOnException tail patch (reference dreamer_v3.py:559-572 adapted):
        after an env crash-restart, the last stored transition becomes a truncation
        boundary. Rare event, tiny keys, so the extra write-fn compile is negligible.
        """
        env_idx = np.asarray(list(env_indices), dtype=np.int64)
        pos = (self._pos[env_idx] - 1) % self._buffer_size
        rows = {
            k: np.full((len(env_idx), *self._meta[k].feat), val, dtype=self._meta[k].dtype)
            for k, val in values.items()
        }
        self._write_rows(rows, env_idx, pos)

    # ----- sample path -----------------------------------------------------------------
    def _gather(self, keys_sig, seq_len: int, n: int):
        """[2, n] (starts; envs) in one transfer -> {k: [n, seq_len, *feat]} in HBM."""
        cache_key = (keys_sig, seq_len, n)
        if cache_key not in self._gather_fns:
            cap = self._buffer_size
            metas = {key: self._meta[key] for key in keys_sig}

            def gather(buf, idx):
                starts, env_idx = idx[0], idx[1]
                row_idx = (starts[:, None] + jnp.arange(seq_len)[None, :]) % cap  # [n, T]

                def one(key, store):
                    m = metas[key]
                    if m.layout == "chunk":
                        out = store[row_idx, env_idx[:, None]]  # [n, T, C, 128]
                        out = out.reshape(n, seq_len, m.padded)[..., : m.flat]
                    else:
                        rowsel = env_idx[:, None] * m.flat + jnp.arange(m.flat)[None, :]  # [n, F]
                        out = store[rowsel[:, None, :], row_idx[:, :, None]]  # [n, T, F]
                    return out.reshape(n, seq_len, *m.feat)

                return {key: one(key, buf[key]) for key in buf}

            self._gather_fns[cache_key] = jax.jit(gather)
        return self._gather_fns[cache_key]

    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        clone: bool = False,
        n_samples: int = 1,
        sequence_length: int = 1,
        **kwargs: Any,
    ) -> Dict[str, jax.Array]:
        """Return ``{k: [n_samples, sequence_length, batch_size, ...]}`` ON DEVICE."""
        del sample_next_obs, clone, kwargs
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0")
        if self._buf is None:
            raise ValueError(f"not enough history for sequence_length={sequence_length}: the buffer is empty")
        filled = self._filled()
        valid_envs = np.nonzero(filled >= sequence_length)[0]
        if len(valid_envs) == 0:
            raise ValueError(
                f"not enough history for sequence_length={sequence_length}: only {int(filled.max())} steps stored"
            )
        n = batch_size * n_samples
        env_idx = valid_envs[self._rng.integers(0, len(valid_envs), size=(n,))]
        span = filled[env_idx] - sequence_length + 1  # per-env count of valid starts
        offsets = (self._rng.random(n) * span).astype(np.int64)
        # full envs: oldest row sits at the write head; anchor there so sequences
        # never cross it (the host SequentialReplayBuffer does the same)
        anchor = np.where(self._full[env_idx], self._pos[env_idx], 0)
        starts = (anchor + offsets) % self._buffer_size
        out = self._gather(tuple(sorted(self._buf)), int(sequence_length), n)(
            self._buf,
            self._put(np.stack([starts, env_idx]).astype(np.int32)),
        )
        # [N, T, *] -> [G, T, B, *] (match the host SequentialReplayBuffer layout)
        return {
            k: jnp.swapaxes(v.reshape(n_samples, batch_size, sequence_length, *v.shape[2:]), 1, 2)
            for k, v in out.items()
        }

    sample_arrays = sample
    sample_tensors = sample

    # ----- checkpointing ---------------------------------------------------------------
    def _check_ckpt_shape(self, logical: Dict[str, np.ndarray]) -> None:
        cap, envs = next(iter(logical.values())).shape[:2]
        if cap != self._buffer_size or envs != self._n_envs:
            raise ValueError(
                f"Checkpointed replay buffer is [{cap} x {envs} envs] but this run is "
                f"configured for [{self._buffer_size} x {self._n_envs} envs]; resume with "
                "the same buffer.size and env.num_envs (a silent reshape would corrupt replay)"
            )

    def state_dict(self) -> Dict[str, Any]:
        host = {k: self._logical_to_host(k) for k in self._buf} if self._buf is not None else None
        return {"buffer": host, "pos": self._pos.copy(), "full": self._full.copy()}

    def load_state_dict(self, state: Dict[str, Any]) -> "DeviceSequentialReplayBuffer":
        if "buffer" not in state:
            raise ValueError(
                "This checkpoint's replay buffer was saved by the host backend; "
                "resume with buffer.device=False (or drop buffer.checkpoint)"
            )
        host = state["buffer"]
        if host is not None:
            if isinstance(host, dict) and host and not isinstance(next(iter(host.values())), np.ndarray):
                raise ValueError("Unrecognized device-buffer checkpoint payload")
            if host:
                # logical [cap, n_envs, *feat] -> physical storage, via the add
                # machinery: allocate, then write every row at pos 0
                self._meta = {}
                self._buf = None
                self._write_fns, self._gather_fns, self._view_fns = {}, {}, {}
                logical = {k: self._narrow(np.asarray(v)) for k, v in host.items()}
                self._check_ckpt_shape(logical)
                self._allocate({k: v[:1] for k, v in logical.items()})
                env_idx = np.arange(self._n_envs, dtype=np.int64)
                rows = next(iter(logical.values())).shape[0]
                self._buf = self._write_fn(rows, self._n_envs, tuple(sorted(logical)))(
                    self._buf,
                    self._put(self._pack(logical, np.zeros(self._n_envs, dtype=np.int64), env_idx)),
                )
        self._pos = np.asarray(state["pos"], dtype=np.int64).copy()
        self._full = np.asarray(state["full"], dtype=bool).copy()
        return self


class ShardedDeviceSequentialReplayBuffer(DeviceSequentialReplayBuffer):
    """HBM replay sharded over a mesh axis: per-device env shards, all-local traffic.

    Data-parallel counterpart of :class:`DeviceSequentialReplayBuffer` (the
    reference's per-rank host buffers at any world size,
    sheeprl/data/buffers.py:529-744): the env axis is mapped onto the mesh's
    ``data`` axis, so each device stores ``n_envs / W`` envs' histories.
    Every data-path op is a ``shard_map`` whose body touches only the local
    shard:

    - writes: the incoming ``[T, n_envs, *]`` block is ``device_put`` with the
      storage sharding (each device receives exactly its envs' columns), then a
      dense masked scatter lands it at each env's write head — no collectives;
    - sampling: each device draws ``batch/W`` sequences from ITS envs and
      gathers them in-shard; the batch comes out already ``[G, T, B]``-sharded
      on the ``data`` axis, exactly the layout the train steps constrain to —
      ZERO bulk host->device or device->device transfer.

    Partial-env writes (episode-boundary resets, crash-restart patches) use the
    same dense write with a per-env mask, so no sparse cross-shard scatter ever
    forms. Uses the same tile-aware physical layouts as the parent (module
    docstring); both layouts shard cleanly on their env-major axis.
    """

    def __init__(self, buffer_size: int, n_envs: int, mesh: Mesh, axis: str = "data"):
        super().__init__(buffer_size, n_envs=n_envs, device=None)
        world = int(mesh.shape[axis])
        if n_envs % world != 0:
            raise ValueError(
                f"buffer.device=True with a {world}-way '{axis}' mesh axis needs "
                f"env.num_envs divisible by {world}, got {n_envs}"
            )
        self._mesh = mesh
        self._axis = axis
        self._world = world
        self._n_local = n_envs // world
        self._vec_sharding = NamedSharding(mesh, P(axis))

    # ----- layout / placement ----------------------------------------------------------
    def _storage_spec(self, key: str) -> P:
        # chunk [cap, n_envs, C, 128] shards the env axis; tminor [n_envs*F, cap]
        # shards its env-major row axis (env blocks are contiguous)
        if self._meta[key].layout == "chunk":
            return P(None, self._axis, None, None)
        return P(self._axis, None)

    def _block_spec(self, key: str) -> P:
        # write blocks: chunk [rows, k, C, 128]; tminor [k, F, rows]
        if self._meta[key].layout == "chunk":
            return P(None, self._axis, None, None)
        return P(self._axis, None, None)

    def _storage_sharding(self, key: str) -> NamedSharding:
        return NamedSharding(self._mesh, self._storage_spec(key))

    def _put_block(self, key: str, v: np.ndarray) -> jax.Array:
        return jax.device_put(v, NamedSharding(self._mesh, self._block_spec(key)))

    def _to_vec(self, v: np.ndarray) -> jax.Array:
        return jax.device_put(np.ascontiguousarray(v), self._vec_sharding)

    def _allocate(self, data: Dict[str, np.ndarray]) -> None:
        buf = {}
        for k, v in data.items():
            leaf = self._narrow(np.asarray(v))
            self._meta[k] = _leaf_meta(tuple(leaf.shape[2:]), leaf.dtype)
            buf[k] = jax.jit(
                partial(jnp.zeros, self._storage_shape(k), leaf.dtype),
                out_shardings=self._storage_sharding(k),
            )()
        self._buf = buf

    def _logical_view(self, key: str) -> jax.Array:
        if key not in self._view_fns:
            self._view_fns[key] = jax.jit(
                self._view_closure(key), out_shardings=NamedSharding(self._mesh, P(None, self._axis))
            )
        return self._view_fns[key](self._buf[key])

    # ----- write path ------------------------------------------------------------------
    def _write_fn(self, rows: int, k_unused: int, keys_sig):
        """Dense masked writer: every env column is written (kept envs keep their
        current value via the mask), so each shard's scatter is purely local."""
        cache_key = (rows, keys_sig)
        if cache_key not in self._write_fns:
            cap = self._buffer_size
            nl = self._n_local
            metas = {key: self._meta[key] for key in keys_sig}

            def body(store_tree, block_tree, pos, mask):
                # per-shard: pos/mask [nl]; chunk store [cap, nl, C, 128] + block
                # [rows, nl, C, 128]; tminor store [nl*F, cap] + block [nl, F, rows]
                row_idx = (pos[None, :] + jnp.arange(rows)[:, None]) % cap  # [rows, nl]
                cols = jnp.arange(nl)

                def one(key, store, new):
                    m = metas[key]
                    if m.layout == "chunk":
                        cur = store[row_idx, cols[None, :]]  # [rows, nl, C, 128]
                        sel = mask.reshape(1, nl, 1, 1)
                        return store.at[row_idx, cols[None, :]].set(
                            jnp.where(sel, new.astype(store.dtype), cur)
                        )
                    rowsel = cols[:, None] * m.flat + jnp.arange(m.flat)[None, :]  # [nl, F]
                    tcols = (pos[:, None] + jnp.arange(rows)[None, :]) % cap  # [nl, rows]
                    cur = store[rowsel[:, :, None], tcols[:, None, :]]  # [nl, F, rows]
                    sel = mask.reshape(nl, 1, 1)
                    return store.at[rowsel[:, :, None], tcols[:, None, :]].set(
                        jnp.where(sel, new.astype(store.dtype), cur)
                    )

                return {key: one(key, store_tree[key], block_tree[key]) for key in store_tree}

            smapped = _shard_map(
                body,
                mesh=self._mesh,
                in_specs=(
                    {key: self._storage_spec(key) for key in keys_sig},
                    {key: self._block_spec(key) for key in keys_sig},
                    P(self._axis),
                    P(self._axis),
                ),
                out_specs={key: self._storage_spec(key) for key in keys_sig},
            )
            self._write_fns[cache_key] = jax.jit(smapped, donate_argnums=(0,))
        return self._write_fns[cache_key]

    def _masked_write(self, data: Dict[str, np.ndarray], pos: np.ndarray, mask: np.ndarray, rows: int) -> None:
        """Write dense ``[rows, n_envs, *feat]`` host blocks where mask."""
        keys_sig = tuple(sorted(data))
        sub = {k: self._buf[k] for k in keys_sig}
        blocks = {k: self._put_block(k, self._to_physical(k, self._narrow(np.asarray(v)))) for k, v in data.items()}
        out = self._write_fn(rows, self._n_envs, keys_sig)(
            sub, blocks, self._to_vec(pos.astype(np.int32)), self._to_vec(mask)
        )
        self._buf.update(out)

    def add(
        self,
        data: Dict[str, np.ndarray],
        indices: Optional[Sequence[int]] = None,
        validate_args: bool = False,
    ) -> None:
        if validate_args:
            from sheeprl_tpu.data.buffers import _validate_added_data

            _validate_added_data(data)
        first = np.asarray(next(iter(data.values())))
        rows = int(first.shape[0])
        if self._buf is None:
            if indices is not None:
                raise RuntimeError("The first add must cover every env (no partial-env add into an empty buffer)")
            self._allocate(data)
        if indices is None:
            env_idx = np.arange(self._n_envs, dtype=np.int64)
            block = {k: np.asarray(v) for k, v in data.items()}
            mask = np.ones(self._n_envs, dtype=bool)
        else:
            env_idx = np.asarray(list(indices), dtype=np.int64)
            mask = np.zeros(self._n_envs, dtype=bool)
            mask[env_idx] = True
            block = {}
            for k, v in data.items():
                v = self._narrow(np.asarray(v))
                dense = np.zeros((rows, self._n_envs, *v.shape[2:]), dtype=v.dtype)
                dense[:, env_idx] = v
                block[k] = dense
        self._masked_write(block, self._pos, mask, rows)
        new_pos = self._pos[env_idx] + rows
        self._full[env_idx] |= new_pos >= self._buffer_size
        self._pos[env_idx] = new_pos % self._buffer_size

    def _write_rows(self, values: Dict[str, np.ndarray], env_idx: np.ndarray, pos: np.ndarray) -> None:
        mask = np.zeros(self._n_envs, dtype=bool)
        mask[env_idx] = True
        dense_pos = np.zeros(self._n_envs, dtype=np.int64)
        dense_pos[env_idx] = pos
        dense = {}
        for k, v in values.items():
            v = self._narrow(np.asarray(v))
            d = np.zeros((1, self._n_envs, *v.shape[1:]), dtype=v.dtype)
            d[0, env_idx] = v
            dense[k] = d
        self._masked_write(dense, dense_pos, mask, 1)

    def _read_row(self, key: str, env_idx: np.ndarray, pos: np.ndarray) -> np.ndarray:
        # full-env reads only (the checkpoint truncated-patch path): each device
        # reads its own envs' rows through the sharded gather
        if len(env_idx) != self._n_envs or not np.array_equal(env_idx, np.arange(self._n_envs)):
            raise ValueError("sharded _read_row reads all envs at once")
        out = self._sharded_gather_fn((key,), 1, 1, self._n_local)(
            {key: self._buf[key]},
            self._to_vec(pos.astype(np.int32)),
            self._to_vec((env_idx % self._n_local).astype(np.int32)),
        )[key]
        return np.asarray(jax.device_get(out))[0, 0]  # [1, 1, n_envs, *feat] -> [n_envs, *feat]

    def load_state_dict(self, state: Dict[str, Any]) -> "ShardedDeviceSequentialReplayBuffer":
        # parent logic re-layouts through _allocate/_write_fn, which here are the
        # sharded implementations; the masked writer wants the dense path
        if "buffer" not in state:
            raise ValueError(
                "This checkpoint's replay buffer was saved by the host backend; "
                "resume with buffer.device=False (or drop buffer.checkpoint)"
            )
        host = state["buffer"]
        if host is not None:
            if isinstance(host, dict) and host and not isinstance(next(iter(host.values())), np.ndarray):
                raise ValueError("Unrecognized device-buffer checkpoint payload")
            if host:
                self._meta = {}
                self._buf = None
                self._write_fns, self._gather_fns, self._view_fns = {}, {}, {}
                logical = {k: self._narrow(np.asarray(v)) for k, v in host.items()}
                self._check_ckpt_shape(logical)
                self._allocate({k: v[:1] for k, v in logical.items()})
                rows = next(iter(logical.values())).shape[0]
                self._masked_write(
                    logical, np.zeros(self._n_envs, dtype=np.int64), np.ones(self._n_envs, dtype=bool), rows
                )
        self._pos = np.asarray(state["pos"], dtype=np.int64).copy()
        self._full = np.asarray(state["full"], dtype=bool).copy()
        return self

    # ----- sample path -----------------------------------------------------------------
    def _sharded_gather_fn(self, keys_sig, seq_len: int, n_samples: int, b_local: int):
        cache_key = (keys_sig, seq_len, n_samples, b_local)
        if cache_key not in self._gather_fns:
            cap = self._buffer_size
            metas = {key: self._meta[key] for key in keys_sig}

            def body(store_tree, starts, env_local):
                # per-shard: starts/env_local [n_samples * b_local], g-major
                row_idx = (starts[:, None] + jnp.arange(seq_len)[None, :]) % cap  # [n, T]

                def one(key, store):
                    m = metas[key]
                    if m.layout == "chunk":
                        out = store[row_idx, env_local[:, None]]  # [n, T, C, 128]
                        out = out.reshape(-1, seq_len, m.padded)[..., : m.flat]
                    else:
                        rowsel = env_local[:, None] * m.flat + jnp.arange(m.flat)[None, :]
                        out = store[rowsel[:, None, :], row_idx[:, :, None]]  # [n, T, F]
                    out = out.reshape(n_samples, b_local, seq_len, m.flat)
                    out = jnp.swapaxes(out, 1, 2)  # [G, T, b_local, F]
                    return out.reshape(n_samples, seq_len, b_local, *m.feat)

                return {key: one(key, store_tree[key]) for key in store_tree}

            out_rank = {key: 3 + len(metas[key].feat) for key in keys_sig}
            smapped = _shard_map(
                body,
                mesh=self._mesh,
                in_specs=(
                    {key: self._storage_spec(key) for key in keys_sig},
                    P(self._axis),
                    P(self._axis),
                ),
                out_specs={
                    key: P(None, None, self._axis, *([None] * (out_rank[key] - 3))) for key in keys_sig
                },
            )
            self._gather_fns[cache_key] = jax.jit(smapped)
        return self._gather_fns[cache_key]

    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        clone: bool = False,
        n_samples: int = 1,
        sequence_length: int = 1,
        **kwargs: Any,
    ) -> Dict[str, jax.Array]:
        """``{k: [n_samples, sequence_length, batch_size, ...]}``, batch axis sharded.

        Each device contributes ``batch_size / W`` sequences drawn from its own
        envs, so the gathered batch lands already laid out for the train step's
        ``P(None, 'data')`` constraint.
        """
        del sample_next_obs, clone, kwargs
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0")
        if batch_size % self._world != 0:
            raise ValueError(
                f"batch_size ({batch_size}) must be divisible by the '{self._axis}' "
                f"mesh axis size ({self._world})"
            )
        if self._buf is None:
            raise ValueError(f"not enough history for sequence_length={sequence_length}: the buffer is empty")
        filled = self._filled()
        b_local = batch_size // self._world
        n_local = b_local * n_samples
        starts = np.empty(self._world * n_local, dtype=np.int32)
        env_local = np.empty(self._world * n_local, dtype=np.int32)
        for d in range(self._world):
            lo = d * self._n_local
            local_filled = filled[lo : lo + self._n_local]
            valid = np.nonzero(local_filled >= sequence_length)[0]
            if len(valid) == 0:
                raise ValueError(
                    f"not enough history for sequence_length={sequence_length}: "
                    f"only {int(local_filled.max())} steps stored on device shard {d}"
                )
            le = valid[self._rng.integers(0, len(valid), size=(n_local,))]
            ge = le + lo  # global env ids for anchor/span lookups
            span = filled[ge] - sequence_length + 1
            offsets = (self._rng.random(n_local) * span).astype(np.int64)
            anchor = np.where(self._full[ge], self._pos[ge], 0)
            sl = slice(d * n_local, (d + 1) * n_local)
            starts[sl] = (anchor + offsets) % self._buffer_size
            env_local[sl] = le
        out = self._sharded_gather_fn(
            tuple(sorted(self._buf)), int(sequence_length), int(n_samples), b_local
        )(self._buf, self._to_vec(starts), self._to_vec(env_local))
        return out

    sample_arrays = sample
    sample_tensors = sample
