"""Replay / rollout buffers: host-numpy storage, JAX device hand-off.

Behavioral parity with reference sheeprl/data/buffers.py — ReplayBuffer (:20),
SequentialReplayBuffer (:363), EnvIndependentReplayBuffer (:529), EpisodeBuffer (:746)
— with the torch bridge (`sample_tensors`, :290-326) replaced by `sample_arrays`,
which lands samples in HBM as (optionally sharded) jax.Arrays.

TPU-first design notes:
- storage stays host-side numpy/memmap in the reference ``[T, n_envs, *]`` layout —
  env interaction is host work, and large off-policy buffers don't fit HBM;
- the only device interaction is `device_put` of sampled batches (overlappable with
  compute via double-buffered prefetch, see sheeprl_tpu/data/prefetch.py);
- samplers use a seedable ``np.random.Generator`` so runs are reproducible.
"""

from __future__ import annotations

import logging
import os
import shutil
import uuid
from itertools import compress
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Type, Union

import numpy as np

from sheeprl_tpu.utils.memmap import MemmapArray
from sheeprl_tpu.utils.utils import NUMPY_TO_JAX_DTYPE


def _native_seq_gather():
    """The C++ fused gather (sheeprl_tpu/native) or None when unavailable."""
    try:
        from sheeprl_tpu.native import native_available, seq_gather
    except Exception:  # pragma: no cover - import/build failure
        return None
    return seq_gather if native_available() else None

_MEMMAP_ERR = (
    'memmap_mode must be one of the writable modes ("r+"/"readwrite", "w+"/"write", '
    '"c"/"copyonwrite") — a read-only mapping cannot back a replay buffer'
)


def get_array(
    array: Union[np.ndarray, MemmapArray],
    dtype=None,
    clone: bool = False,
    device: Optional[Any] = None,
):
    """numpy -> jax.Array bridge (reference counterpart: get_tensor, buffers.py:1158-1180).

    ``device`` may be a jax.Device, a Sharding, or None (host numpy passthrough).
    float64/int64 are narrowed to f32/i32 (TPU-native widths).
    """
    if isinstance(array, MemmapArray):
        array = array.array
    if clone and device is None:
        array = array.copy()
    if device is None:
        return array if dtype is None else array.astype(dtype)
    import jax

    if dtype is None:
        dtype = NUMPY_TO_JAX_DTYPE.get(np.dtype(array.dtype), None)
    if dtype is not None:
        array = np.asarray(array, dtype=dtype)
    # Sharded host->device puts run through jax's batched_device_put, which blocks
    # until the copy lands — a synchronous host<->device round trip per call.
    # A 1-device mesh's NamedSharding is equivalent to its single device, and a
    # plain-device put is fully asynchronous: unwrap so transfers overlap compute.
    if isinstance(device, jax.sharding.Sharding):
        device_set = device.device_set
        if len(device_set) == 1:
            device = next(iter(device_set))
    return jax.device_put(array, device)


def _validate_added_data(data: Dict[str, np.ndarray]) -> None:
    if not isinstance(data, dict):
        raise ValueError(f"expected a dict of numpy arrays to add, not a {type(data)}")
    for k, v in data.items():
        if not isinstance(v, np.ndarray):
            raise ValueError(
                f"expected a dict of numpy arrays to add; key '{k}' holds a {type(v)} instead"
            )
    shapes = {k: v.shape[:2] for k, v in data.items() if len(v.shape) >= 2}
    for k, v in data.items():
        if len(v.shape) < 2:
            raise RuntimeError(
                f"added arrays need a [time, env, ...] layout (>= 2 dims); '{k}' arrived with shape {v.shape}"
            )
    if len(set(shapes.values())) > 1:
        raise RuntimeError(
            f"all added arrays must agree on their leading [time, env] dims; got "
            f"{ {k: s for k, s in shapes.items()} }"
        )


class ReplayBuffer:
    """Circular dict-of-arrays buffer with layout ``[buffer_size, n_envs, *]``.

    Reference: sheeprl/data/buffers.py:20-360.
    """

    batch_axis: int = 1

    def __init__(
        self,
        buffer_size: int,
        n_envs: int = 1,
        obs_keys: Sequence[str] = ("observations",),
        memmap: bool = False,
        memmap_dir: Union[str, os.PathLike, None] = None,
        memmap_mode: str = "r+",
        seed: Optional[int] = None,
        **kwargs: Any,
    ):
        if buffer_size <= 0:
            raise ValueError(f"a replay buffer needs a positive capacity; received buffer_size={buffer_size}")
        if n_envs <= 0:
            raise ValueError(f"a replay buffer needs at least one env stream; received n_envs={n_envs}")
        self._buffer_size = buffer_size
        self._n_envs = n_envs
        self._obs_keys = tuple(obs_keys)
        self._memmap = memmap
        self._memmap_dir = memmap_dir
        self._memmap_mode = memmap_mode
        if memmap:
            if memmap_mode not in ("r+", "w+", "c", "copyonwrite", "readwrite", "write"):
                raise ValueError(_MEMMAP_ERR)
            if memmap_dir is None:
                raise ValueError(
                    "memmap=True needs a target directory: pass memmap_dir (it is currently None)"
                )
            self._memmap_dir = Path(memmap_dir)
            self._memmap_dir.mkdir(parents=True, exist_ok=True)
        self._buf: Dict[str, Union[np.ndarray, MemmapArray]] = {}
        self._pos = 0
        self._full = False
        self._rng: np.random.Generator = np.random.default_rng(seed)

    # ----- introspection -------------------------------------------------------------
    @property
    def buffer(self) -> Dict[str, np.ndarray]:
        return self._buf

    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def full(self) -> bool:
        return self._full

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def empty(self) -> bool:
        return self._buf is None or len(self._buf) == 0

    @property
    def is_memmap(self) -> bool:
        return self._memmap

    def __len__(self) -> int:
        return self._buffer_size

    def seed(self, seed: Optional[int]) -> None:
        self._rng = np.random.default_rng(seed)

    # ----- writes --------------------------------------------------------------------
    def _allocate(self, key: str, sample_shape: Sequence[int], dtype) -> Union[np.ndarray, MemmapArray]:
        full_shape = (self._buffer_size, self._n_envs, *sample_shape)
        if self._memmap:
            return MemmapArray(
                filename=Path(self._memmap_dir) / f"{key}.memmap",
                dtype=dtype,
                shape=full_shape,
                mode=self._memmap_mode,
            )
        return np.empty(full_shape, dtype=dtype)

    def add(self, data: Union["ReplayBuffer", Dict[str, np.ndarray]], validate_args: bool = False) -> None:
        """Append ``[T, n_envs, *]`` data, overwriting the oldest rows when full."""
        if isinstance(data, ReplayBuffer):
            data = data.buffer
        if validate_args:
            _validate_added_data(data)
        data_len = next(iter(data.values())).shape[0]
        next_pos = (self._pos + data_len) % self._buffer_size
        if next_pos <= self._pos or (data_len > self._buffer_size and not self._full):
            idxes = np.concatenate([np.arange(self._pos, self._buffer_size), np.arange(0, next_pos)])
        else:
            idxes = np.arange(self._pos, next_pos)
        if data_len > self._buffer_size:
            data = {k: v[-self._buffer_size - next_pos :] for k, v in data.items()}
        if self.empty:
            for k, v in data.items():
                self._buf[k] = self._allocate(k, v.shape[2:], v.dtype)
        for k, v in data.items():
            self._buf[k][idxes] = v
        if self._pos + data_len >= self._buffer_size:
            self._full = True
        self._pos = next_pos

    def __getitem__(self, key: str) -> Union[np.ndarray, MemmapArray]:
        if not isinstance(key, str):
            raise TypeError("buffer keys are strings; got a non-string key")
        if self.empty:
            raise RuntimeError("empty buffer: nothing has been added yet, so there is no storage to read")
        return self._buf.get(key)

    def __setitem__(self, key: str, value: Union[np.ndarray, np.memmap, MemmapArray]) -> None:
        if not isinstance(value, (np.ndarray, MemmapArray)):
            raise ValueError(
                f"only ndarray/memmap/MemmapArray values can be stored; got {type(value)}"
            )
        if self.empty:
            raise RuntimeError("empty buffer: nothing has been added yet, so there is no storage to read")
        if value.shape[:2] != (self._buffer_size, self._n_envs):
            raise RuntimeError(
                f"stored arrays need a [capacity, env, ...] layout (>= 2 dims); got shape {value.shape}"
            )
        if self._memmap:
            filename = value.filename if isinstance(value, MemmapArray) else Path(self._memmap_dir) / f"{key}.memmap"
            self._buf[key] = MemmapArray.from_array(value, filename=filename, mode=self._memmap_mode)
        else:
            self._buf[key] = np.copy(value.array if isinstance(value, MemmapArray) else value)

    # ----- reads ---------------------------------------------------------------------
    def to_arrays(self, dtype=None, clone: bool = False, device=None) -> Dict[str, Any]:
        """Whole-buffer conversion (reference ``to_tensor``, buffers.py:108-135)."""
        return {k: get_array(v, dtype=dtype, clone=clone, device=device) for k, v in self._buf.items()}

    # kept as an alias so reference-style call sites read naturally
    to_tensor = to_arrays

    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        clone: bool = False,
        n_samples: int = 1,
        **kwargs: Any,
    ) -> Dict[str, np.ndarray]:
        """Uniform sampling; output shape ``[n_samples, batch_size, *]``.

        When ``sample_next_obs`` the most recent position is excluded so ``next_*``
        never crosses the write head (reference buffers.py:223-268).
        """
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(f"sampling needs positive batch_size and n_samples; got batch_size={batch_size}, n_samples={n_samples}")
        if not self._full and self._pos == 0:
            raise ValueError(
                "cannot sample from an empty buffer: add at least one transition first"
            )
        if self._full:
            first_range_end = self._pos - 1 if sample_next_obs else self._pos
            second_range_end = self._buffer_size if first_range_end >= 0 else self._buffer_size + first_range_end
            valid = np.concatenate(
                [np.arange(0, max(first_range_end, 0)), np.arange(self._pos, second_range_end)]
            ).astype(np.intp)
            batch_idxes = valid[self._rng.integers(0, len(valid), size=(batch_size * n_samples,), dtype=np.intp)]
        else:
            max_pos = self._pos - 1 if sample_next_obs else self._pos
            if max_pos == 0:
                raise RuntimeError(
                    "sample_next_obs needs two stored steps (obs and its successor); the buffer holds only one"
                )
            batch_idxes = self._rng.integers(0, max_pos, size=(batch_size * n_samples,), dtype=np.intp)
        flat = self._gather(batch_idxes, sample_next_obs=sample_next_obs, clone=clone)
        return {k: v.reshape(n_samples, batch_size, *v.shape[1:]) for k, v in flat.items()}

    def _gather(self, batch_idxes: np.ndarray, sample_next_obs: bool = False, clone: bool = False):
        if self.empty:
            raise RuntimeError("empty buffer: nothing has been added yet, so there is no storage to read")
        env_idxes = self._rng.integers(0, self._n_envs, size=(len(batch_idxes),), dtype=np.intp)
        flat_idx = batch_idxes * self._n_envs + env_idxes
        if sample_next_obs:
            flat_next = ((batch_idxes + 1) % self._buffer_size) * self._n_envs + env_idxes
        out: Dict[str, np.ndarray] = {}
        for k, v in self._buf.items():
            flat_v = np.reshape(v, (-1, *v.shape[2:]))
            out[k] = np.take(flat_v, flat_idx, axis=0)
            if clone:
                out[k] = out[k].copy()
            if sample_next_obs and k in self._obs_keys:
                out[f"next_{k}"] = np.take(flat_v, flat_next, axis=0)
                if clone:
                    out[f"next_{k}"] = out[f"next_{k}"].copy()
        return out

    def sample_arrays(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        clone: bool = False,
        dtype=None,
        device=None,
        **kwargs: Any,
    ) -> Dict[str, Any]:
        """Sample then move to device (reference ``sample_tensors``, buffers.py:290-326)."""
        n_samples = kwargs.pop("n_samples", 1)
        samples = self.sample(
            batch_size=batch_size, sample_next_obs=sample_next_obs, clone=clone, n_samples=n_samples, **kwargs
        )
        return {k: get_array(v, dtype=dtype, device=device) for k, v in samples.items()}

    sample_tensors = sample_arrays

    # ----- checkpoint support ----------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        return {
            "buffer": {k: np.asarray(v) for k, v in self._buf.items()},
            "pos": self._pos,
            "full": self._full,
        }

    def load_state_dict(self, state: Dict[str, Any]) -> "ReplayBuffer":
        for k, v in state["buffer"].items():
            if self._memmap:
                self._buf[k] = MemmapArray.from_array(
                    v, filename=Path(self._memmap_dir) / f"{k}.memmap", mode=self._memmap_mode
                )
            else:
                self._buf[k] = np.array(v)
        self._pos = state["pos"]
        self._full = state["full"]
        return self

    def _patch_truncated(self):
        """Force the last written step of every env to 'truncated'; return undo state."""
        if self.empty or "truncated" not in self._buf:
            return None
        last = (self._pos - 1) % self._buffer_size
        original = np.array(self._buf["truncated"][last])
        self._buf["truncated"][last] = np.where(self._buf["terminated"][last], 0, 1)
        return (last, original)

    def _unpatch_truncated(self, undo) -> None:
        if undo is None:
            return
        last, original = undo
        self._buf["truncated"][last] = original


class SequentialReplayBuffer(ReplayBuffer):
    """Samples contiguous length-L windows ignoring episode bounds.

    Output ``[n_samples, sequence_length, batch_size, *]``; start indices avoid the
    in-write region (reference buffers.py:363-526).
    """

    batch_axis: int = 2

    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        clone: bool = False,
        n_samples: int = 1,
        sequence_length: int = 1,
        **kwargs: Any,
    ) -> Dict[str, np.ndarray]:
        batch_dim = batch_size * n_samples
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(f"sampling needs positive batch_size and n_samples; got batch_size={batch_size}, n_samples={n_samples}")
        if not self._full and self._pos == 0:
            raise ValueError(
                "cannot sample from an empty buffer: add at least one transition first"
            )
        if not self._full and self._pos - sequence_length + 1 < 1:
            raise ValueError(f"not enough history for sequence_length={sequence_length}: only {self._pos} steps stored")
        if self._full and sequence_length > self._buffer_size:
            raise ValueError(
                f"sequence_length={sequence_length} cannot exceed the buffer capacity ({self._buffer_size})"
            )
        if self._full:
            first_range_end = self._pos - sequence_length + 1
            second_range_end = self._buffer_size if first_range_end >= 0 else self._buffer_size + first_range_end
            valid = np.concatenate(
                [np.arange(0, max(first_range_end, 0)), np.arange(self._pos, second_range_end)]
            ).astype(np.intp)
            start_idxes = valid[self._rng.integers(0, len(valid), size=(batch_dim,), dtype=np.intp)]
        else:
            start_idxes = self._rng.integers(0, self._pos - sequence_length + 1, size=(batch_dim,), dtype=np.intp)
        offsets = np.arange(sequence_length, dtype=np.intp)[None, :]
        idxes = (start_idxes[:, None] + offsets) % self._buffer_size
        return self._gather_sequences(
            idxes, batch_size, n_samples, sequence_length, sample_next_obs=sample_next_obs, clone=clone
        )

    def _gather_sequences(
        self,
        batch_idxes: np.ndarray,
        batch_size: int,
        n_samples: int,
        sequence_length: int,
        sample_next_obs: bool = False,
        clone: bool = False,
    ) -> Dict[str, np.ndarray]:
        # every element of a sequence must come from the same env stream
        if self._n_envs == 1:
            pair_envs = np.zeros((batch_size * n_samples,), dtype=np.intp)
        else:
            pair_envs = self._rng.integers(0, self._n_envs, size=(batch_size * n_samples,), dtype=np.intp)

        # Native fused gather+transpose (sheeprl_tpu/native): one multithreaded
        # pass writing the final [n_samples, L, B, *] layout. Falls back to the
        # numpy path when the extension is unavailable.
        native = _native_seq_gather()
        if native is not None:
            srcs = {k: np.asarray(v) for k, v in self._buf.items()}
            if all(s.flags["C_CONTIGUOUS"] for s in srcs.values()):
                starts = np.ascontiguousarray(batch_idxes[:, 0], dtype=np.int64)
                envs64 = pair_envs.astype(np.int64)
                next_starts = (starts + 1) % self._buffer_size if sample_next_obs else None
                out: Dict[str, np.ndarray] = {}
                for k, src in srcs.items():
                    out[k] = native(src, starts, envs64, n_samples, batch_size, sequence_length)
                    if sample_next_obs:
                        out[f"next_{k}"] = native(
                            src, next_starts, envs64, n_samples, batch_size, sequence_length
                        )
                return out

        return self._gather_sequences_numpy(
            batch_idxes, pair_envs, batch_size, n_samples, sequence_length, sample_next_obs, clone
        )

    def _gather_sequences_numpy(
        self,
        batch_idxes: np.ndarray,
        pair_envs: np.ndarray,
        batch_size: int,
        n_samples: int,
        sequence_length: int,
        sample_next_obs: bool = False,
        clone: bool = False,
    ) -> Dict[str, np.ndarray]:
        flat_batch_idxes = np.ravel(batch_idxes)
        env_idxes = np.repeat(pair_envs, sequence_length)
        flat_idx = flat_batch_idxes * self._n_envs + env_idxes
        out: Dict[str, np.ndarray] = {}
        for k, v in self._buf.items():
            flat_v = np.take(np.reshape(v, (-1, *v.shape[2:])), flat_idx, axis=0)
            batched = np.reshape(flat_v, (n_samples, batch_size, sequence_length) + flat_v.shape[1:])
            out[k] = np.swapaxes(batched, 1, 2)
            if clone:
                out[k] = out[k].copy()
            if sample_next_obs:
                flat_next = np.asarray(v)[(flat_batch_idxes + 1) % self._buffer_size, env_idxes]
                batched_next = np.reshape(flat_next, (n_samples, batch_size, sequence_length) + flat_next.shape[1:])
                out[f"next_{k}"] = np.swapaxes(batched_next, 1, 2)
                if clone:
                    out[f"next_{k}"] = out[f"next_{k}"].copy()
        return out


class EnvIndependentReplayBuffer:
    """One sub-buffer per env so per-env streams stay contiguous.

    Sampling multinomially splits the batch across sub-buffers and concatenates on
    ``buffer_cls.batch_axis`` (reference buffers.py:529-744).
    """

    def __init__(
        self,
        buffer_size: int,
        n_envs: int = 1,
        obs_keys: Sequence[str] = ("observations",),
        memmap: bool = False,
        memmap_dir: Union[str, os.PathLike, None] = None,
        memmap_mode: str = "r+",
        buffer_cls: Type[ReplayBuffer] = ReplayBuffer,
        seed: Optional[int] = None,
        **kwargs: Any,
    ):
        if buffer_size <= 0:
            raise ValueError(f"a replay buffer needs a positive capacity; received buffer_size={buffer_size}")
        if n_envs <= 0:
            raise ValueError(f"a replay buffer needs at least one env stream; received n_envs={n_envs}")
        if memmap:
            if memmap_mode not in ("r+", "w+", "c", "copyonwrite", "readwrite", "write"):
                raise ValueError(_MEMMAP_ERR)
            if memmap_dir is None:
                raise ValueError(
                    "memmap=True needs a target directory: pass memmap_dir (it is currently None)"
                )
            memmap_dir = Path(memmap_dir)
            memmap_dir.mkdir(parents=True, exist_ok=True)
        self._buf: List[ReplayBuffer] = [
            buffer_cls(
                buffer_size=buffer_size,
                n_envs=1,
                obs_keys=obs_keys,
                memmap=memmap,
                memmap_dir=memmap_dir / f"env_{i}" if memmap else None,
                memmap_mode=memmap_mode,
                **kwargs,
            )
            for i in range(n_envs)
        ]
        self._buffer_size = buffer_size
        self._n_envs = n_envs
        self._rng: np.random.Generator = np.random.default_rng(seed)
        self._concat_along_axis = buffer_cls.batch_axis

    @property
    def buffer(self) -> Sequence[ReplayBuffer]:
        return tuple(self._buf)

    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def full(self) -> Sequence[bool]:
        return tuple(b.full for b in self._buf)

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def empty(self) -> Sequence[bool]:
        return tuple(b.empty for b in self._buf)

    @property
    def is_memmap(self) -> Sequence[bool]:
        return tuple(b.is_memmap for b in self._buf)

    def __len__(self) -> int:
        return self._buffer_size

    def seed(self, seed: Optional[int]) -> None:
        self._rng = np.random.default_rng(seed)
        for i, b in enumerate(self._buf):
            b.seed(None if seed is None else seed + i + 1)

    def add(
        self,
        data: Union[ReplayBuffer, Dict[str, np.ndarray]],
        indices: Optional[Sequence[int]] = None,
        validate_args: bool = False,
    ) -> None:
        if isinstance(data, ReplayBuffer):
            data = data.buffer
        if indices is None:
            indices = tuple(range(self._n_envs))
        elif len(indices) != next(iter(data.values())).shape[1]:
            raise ValueError(
                f"got {len(indices)} env indices for arrays carrying "
                f"{next(iter(data.values())).shape[1]} env columns; they must match"
            )
        for data_col, env_idx in enumerate(indices):
            self._buf[env_idx].add({k: v[:, data_col : data_col + 1] for k, v in data.items()}, validate_args)

    def patch_last(self, env_indices: Sequence[int], values: Dict[str, float]) -> None:
        """Overwrite scalar keys of the most recent row of the given envs.

        The RestartOnException tail patch (same surface as
        ``DeviceSequentialReplayBuffer.patch_last``): after an env crash-restart,
        the last stored transition becomes a truncation boundary.
        """
        for i in env_indices:
            b = self._buf[i]
            last = (b._pos - 1) % b.buffer_size
            for k, val in values.items():
                b[k][last] = np.full_like(b[k][last], val)

    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        clone: bool = False,
        n_samples: int = 1,
        **kwargs: Any,
    ) -> Dict[str, np.ndarray]:
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(f"sampling needs positive batch_size and n_samples; got batch_size={batch_size}, n_samples={n_samples}")
        bs_per_buf = np.bincount(self._rng.integers(0, self._n_envs, (batch_size,)))
        parts = [
            b.sample(batch_size=bs, sample_next_obs=sample_next_obs, clone=clone, n_samples=n_samples, **kwargs)
            for b, bs in zip(self._buf, bs_per_buf)
            if bs > 0
        ]
        return {k: np.concatenate([p[k] for p in parts], axis=self._concat_along_axis) for k in parts[0].keys()}

    def sample_arrays(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        clone: bool = False,
        n_samples: int = 1,
        dtype=None,
        device=None,
        **kwargs: Any,
    ) -> Dict[str, Any]:
        samples = self.sample(
            batch_size=batch_size, sample_next_obs=sample_next_obs, clone=clone, n_samples=n_samples, **kwargs
        )
        return {k: get_array(v, dtype=dtype, device=device) for k, v in samples.items()}

    sample_tensors = sample_arrays

    def state_dict(self) -> Dict[str, Any]:
        return {"buffers": [b.state_dict() for b in self._buf]}

    def load_state_dict(self, state: Dict[str, Any]) -> "EnvIndependentReplayBuffer":
        if "buffers" not in state:
            raise ValueError(
                "This checkpoint's replay buffer was saved by the device (HBM) "
                "backend; resume with buffer.device=True (or drop buffer.checkpoint)"
            )
        for b, s in zip(self._buf, state["buffers"]):
            b.load_state_dict(s)
        return self


class EpisodeBuffer:
    """Whole-episode storage with per-env open-episode accounting.

    Reference: sheeprl/data/buffers.py:746-1156 — same eviction (oldest episodes until
    the new one fits), ``prioritize_ends`` sampling, and minimum-length checks.
    """

    batch_axis: int = 2

    def __init__(
        self,
        buffer_size: int,
        minimum_episode_length: int,
        n_envs: int = 1,
        obs_keys: Sequence[str] = ("observations",),
        prioritize_ends: bool = False,
        memmap: bool = False,
        memmap_dir: Union[str, os.PathLike, None] = None,
        memmap_mode: str = "r+",
        seed: Optional[int] = None,
    ) -> None:
        if buffer_size <= 0:
            raise ValueError(f"a replay buffer needs a positive capacity; received buffer_size={buffer_size}")
        if minimum_episode_length <= 0:
            raise ValueError(f"the minimum episode length must be positive; received {minimum_episode_length}")
        if buffer_size < minimum_episode_length:
            raise ValueError(
                f"the minimum episode length ({minimum_episode_length}) must fit inside the "
                f"buffer capacity ({buffer_size})"
            )
        self._n_envs = n_envs
        self._obs_keys = tuple(obs_keys)
        self._buffer_size = buffer_size
        self._minimum_episode_length = minimum_episode_length
        self._prioritize_ends = prioritize_ends
        self._open_episodes: List[List[Dict[str, np.ndarray]]] = [[] for _ in range(n_envs)]
        self._cum_lengths: List[int] = []
        self._buf: List[Dict[str, Union[np.ndarray, MemmapArray]]] = []
        self._rng: np.random.Generator = np.random.default_rng(seed)
        self._memmap = memmap
        self._memmap_dir = memmap_dir
        self._memmap_mode = memmap_mode
        if memmap:
            if memmap_mode not in ("r+", "w+", "c", "copyonwrite", "readwrite", "write"):
                raise ValueError(_MEMMAP_ERR)
            if memmap_dir is None:
                raise ValueError(
                    "memmap=True needs a target directory: pass memmap_dir (it is currently None)"
                )
            self._memmap_dir = Path(memmap_dir)
            self._memmap_dir.mkdir(parents=True, exist_ok=True)

    @property
    def prioritize_ends(self) -> bool:
        return self._prioritize_ends

    @prioritize_ends.setter
    def prioritize_ends(self, value: bool) -> None:
        self._prioritize_ends = value

    @property
    def buffer(self) -> Sequence[Dict[str, np.ndarray]]:
        return self._buf

    @property
    def obs_keys(self) -> Sequence[str]:
        return self._obs_keys

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def buffer_size(self) -> int:
        return self._buffer_size

    @property
    def minimum_episode_length(self) -> int:
        return self._minimum_episode_length

    @property
    def is_memmap(self) -> bool:
        return self._memmap

    @property
    def full(self) -> bool:
        return self._cum_lengths[-1] + self._minimum_episode_length > self._buffer_size if self._buf else False

    def __len__(self) -> int:
        return self._cum_lengths[-1] if self._buf else 0

    def seed(self, seed: Optional[int]) -> None:
        self._rng = np.random.default_rng(seed)

    def add(
        self,
        data: Union[ReplayBuffer, Dict[str, np.ndarray]],
        env_idxes: Optional[Sequence[int]] = None,
        validate_args: bool = False,
    ) -> None:
        if isinstance(data, ReplayBuffer):
            data = data.buffer
        if validate_args:
            if data is None:
                raise ValueError("cannot add a None transition to the episode buffer")
            _validate_added_data(data)
            if "terminated" not in data or "truncated" not in data:
                raise RuntimeError(
                    f"episode steps need both 'terminated' and 'truncated' flags; received keys {data.keys()}"
                )
            if env_idxes is not None and (np.array(env_idxes) >= self._n_envs).any():
                raise ValueError(
                    f"env indices must be ints within [0, {self._n_envs}); received {env_idxes}"
                )
        if env_idxes is None:
            env_idxes = range(self._n_envs)
        for data_col, env in enumerate(env_idxes):
            env_data = {k: v[:, data_col] for k, v in data.items()}
            done = np.logical_or(env_data["terminated"], env_data["truncated"])
            ends = done.nonzero()[0].tolist()
            if not ends:
                self._open_episodes[env].append(env_data)
                continue
            ends.append(len(done))
            start = 0
            for stop in ends:
                chunk = {k: env_data[k][start : stop + 1] for k in env_data.keys()}
                if len(np.logical_or(chunk["terminated"], chunk["truncated"])) > 0:
                    self._open_episodes[env].append(chunk)
                start = stop + 1
                if self._open_episodes[env] and bool(
                    np.logical_or(
                        self._open_episodes[env][-1]["terminated"][-1],
                        self._open_episodes[env][-1]["truncated"][-1],
                    )
                ):
                    self._save_episode(self._open_episodes[env])
                    self._open_episodes[env] = []

    def _save_episode(self, episode_chunks: Sequence[Dict[str, np.ndarray]]) -> None:
        if len(episode_chunks) == 0:
            raise RuntimeError("refusing to store a zero-length episode")
        episode = {
            k: np.concatenate([chunk[k] for chunk in episode_chunks], axis=0) for k in episode_chunks[0].keys()
        }
        ends = np.logical_or(episode["terminated"], episode["truncated"])
        ep_len = ends.shape[0]
        n_dones = len(ends.nonzero()[0])
        if n_dones != 1 or not ends[-1]:
            raise RuntimeError(f"a stored episode must end exactly once; this one has {n_dones} done flags")
        if ep_len < self._minimum_episode_length:
            raise RuntimeError(
                f"episode of {ep_len} steps is below the {self._minimum_episode_length}-step minimum"
            )
        if ep_len > self._buffer_size:
            raise RuntimeError(f"episode of {ep_len} steps exceeds the buffer capacity of {self._buffer_size}")

        if self.full or len(self) + ep_len > self._buffer_size:
            cum = np.array(self._cum_lengths)
            evict_upto = int(((len(self) - cum + ep_len) <= self._buffer_size).argmax())
            if self._memmap and self._memmap_dir is not None:
                for _ in range(evict_upto + 1):
                    victim = self._buf.pop(0)
                    dirname = os.path.dirname(str(victim[next(iter(victim.keys()))].filename))
                    victim.clear()
                    try:
                        shutil.rmtree(dirname)
                    except Exception as e:  # pragma: no cover - best-effort cleanup
                        logging.error(e)
            else:
                self._buf = self._buf[evict_upto + 1 :]
            cum = cum[evict_upto + 1 :] - cum[evict_upto]
            self._cum_lengths = cum.tolist()
        self._cum_lengths.append(len(self) + ep_len)
        if self._memmap:
            episode_dir = Path(self._memmap_dir) / f"episode_{uuid.uuid4()}"
            episode_dir.mkdir(parents=True, exist_ok=True)
            stored = {}
            for k, v in episode.items():
                stored[k] = MemmapArray(
                    filename=str(episode_dir / f"{k}.memmap"), dtype=v.dtype, shape=v.shape, mode=self._memmap_mode
                )
                stored[k][:] = v
            episode = stored
        self._buf.append(episode)

    def sample(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        n_samples: int = 1,
        clone: bool = False,
        sequence_length: int = 1,
        **kwargs: Any,
    ) -> Dict[str, np.ndarray]:
        if batch_size <= 0:
            raise ValueError(f"sampling needs a positive batch_size; received {batch_size}")
        if n_samples <= 0:
            raise ValueError(f"sampling needs a positive n_samples; received {n_samples}")
        lengths = np.array(self._cum_lengths) - np.array([0] + self._cum_lengths[:-1])
        valid_mask = lengths > sequence_length if sample_next_obs else lengths >= sequence_length
        valid_episodes = list(compress(self._buf, valid_mask))
        if len(valid_episodes) == 0:
            raise RuntimeError(
                f"no stored episode is long enough to cut a {sequence_length}-step window from; "
                "add longer episodes first"
            )
        offsets = np.arange(sequence_length, dtype=np.intp)[None, :]
        counts = np.bincount(self._rng.integers(0, len(valid_episodes), (batch_size * n_samples,))).astype(np.intp)
        gathered: Dict[str, List[np.ndarray]] = {k: [] for k in valid_episodes[0].keys()}
        if sample_next_obs:
            gathered.update({f"next_{k}": [] for k in self._obs_keys})
        for i, n in enumerate(counts):
            if n <= 0:
                continue
            ep = valid_episodes[i]
            ep_len = np.logical_or(ep["terminated"], ep["truncated"]).shape[0]
            if sample_next_obs:
                ep_len -= 1
            upper = ep_len - sequence_length + 1
            if self._prioritize_ends:
                upper += sequence_length
            starts = np.minimum(
                self._rng.integers(0, upper, size=(n,)).reshape(-1, 1), ep_len - sequence_length, dtype=np.intp
            )
            indices = starts + offsets
            for k in valid_episodes[0].keys():
                arr = np.asarray(ep[k])
                gathered[k].append(
                    np.take(arr, indices.ravel(), axis=0).reshape(n, sequence_length, *arr.shape[1:])
                )
                if sample_next_obs and k in self._obs_keys:
                    gathered[f"next_{k}"].append(arr[indices + 1])
        out: Dict[str, np.ndarray] = {}
        for k, v in gathered.items():
            if v:
                stacked = np.concatenate(v, axis=0).reshape(n_samples, batch_size, sequence_length, *v[0].shape[2:])
                out[k] = np.moveaxis(stacked, 2, 1)
                if clone:
                    out[k] = out[k].copy()
        return out

    def sample_arrays(
        self,
        batch_size: int,
        sample_next_obs: bool = False,
        n_samples: int = 1,
        clone: bool = False,
        sequence_length: int = 1,
        dtype=None,
        device=None,
        **kwargs: Any,
    ) -> Dict[str, Any]:
        samples = self.sample(batch_size, sample_next_obs, n_samples, clone, sequence_length)
        return {k: get_array(v, dtype=dtype, device=device) for k, v in samples.items()}

    sample_tensors = sample_arrays

    def state_dict(self) -> Dict[str, Any]:
        return {
            "buffer": [{k: np.asarray(v) for k, v in ep.items()} for ep in self._buf],
            "cum_lengths": list(self._cum_lengths),
            "open_episodes": self._open_episodes,
        }

    def load_state_dict(self, state: Dict[str, Any]) -> "EpisodeBuffer":
        self._buf = [dict(ep) for ep in state["buffer"]]
        self._cum_lengths = list(state["cum_lengths"])
        self._open_episodes = state["open_episodes"]
        return self
