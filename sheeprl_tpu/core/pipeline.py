"""Software-pipelined interaction: overlap env stepping, device inference, host work.

The serial interaction loop (reference ``algos/dreamer_v3/dreamer_v3.py:646-677``)
alternates three mutually idle phases per step: the device idles while env workers
step, the env workers idle while the host does bookkeeping, and both idle while the
policy runs. Podracer/Sebulba (Hessel et al., 2021) and EnvPool (Weng et al., 2022)
show that software-pipelining these phases is worth 2-5x actor throughput on exactly
this host-device split. This module provides the two building blocks every training
loop uses:

- :class:`AsyncEnvStepper` splits ``envs.step`` into ``step_async``/``step_wait`` so
  the env workers run while the host processes the PREVIOUS step (buffer writes,
  episode accounting, reset handling) and dispatches device work for the current one.
  Sync vector envs (or ``pipeline=False`` parity runs) fall back to a deferred
  synchronous step with identical call-site semantics.
- :class:`PackedObsCodec` replaces the per-key ``device_put`` of ``prepare_obs`` with
  ONE packed ``device_put`` per step (the same byte-packing fusion as
  ``DeviceRolloutBuffer.add_env``: every transfer carries a fixed cost),
  unpacked and normalized IN-GRAPH inside the jitted act function.
  uint8 pixel stacks travel as raw bytes (4x smaller than the float path) and become
  centered floats on device. The codec can piggyback extra float leaves (rewards /
  dones of the previous step) on the same transfer, so a steady-state pipelined
  iteration performs exactly one host->device put and one device->host action fetch.

In steady state the per-step timeline is::

    encode+put obs_t (+ env products of t-1)      # ONE host->device transfer
    dispatch act(t)                               # async device work
    fetch actions_t                               # the ONE blocking sync
    envs.step_async(actions_t)                    # env workers start stepping
    ... overlap window: buffer writes for t-1/t, episode metrics, resets ...
    obs_{t+1} = envs.step_wait()                  # usually already done
"""

from __future__ import annotations

import time
from typing import Any, Dict, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["AsyncEnvStepper", "PackedObsCodec", "pipeline_enabled", "process_overlap_totals"]

# process-wide cumulative (overlap seconds, overlapped steps) across every stepper;
# never reset — harnesses (bench.py --smoke) read a delta around a run to surface
# the pipeline win even when metric logging is disabled
_PROCESS_OVERLAP = [0.0, 0]


def process_overlap_totals() -> Tuple[float, int]:
    """Cumulative (overlap seconds, steps) across all AsyncEnvSteppers in-process."""
    return _PROCESS_OVERLAP[0], _PROCESS_OVERLAP[1]


def pipeline_enabled(cfg: Any) -> bool:
    """The ``algo.interaction_pipeline`` knob (default ON; absent in old configs)."""
    try:
        return bool(cfg.algo.get("interaction_pipeline", True))
    except AttributeError:  # plain dict-like cfg in tests
        return bool(getattr(cfg.algo, "interaction_pipeline", True))


class AsyncEnvStepper:
    """``step_async``/``step_wait`` facade over any vector env, with serial fallback.

    Pipelining engages only when BOTH the wrapped env supports the async split
    (``AsyncVectorEnv`` / ``SupervisedVectorEnv`` over async workers) and the
    caller asked for it; otherwise ``step_async`` just parks the actions and
    ``step_wait`` runs the ordinary blocking ``step`` — call sites are written
    once against the split API and behave identically (parity runs use
    ``enabled=False``).

    The wall-clock spent between dispatch and wait is the pipeline's overlap
    window — env stepping hidden behind device/host work — accumulated here and
    drained at log boundaries into ``Time/sps_pipeline_overlap``.
    """

    def __init__(self, envs: Any, enabled: bool = True):
        self.envs = envs
        supports = getattr(envs, "supports_step_async", None)
        if supports is None:
            supports = callable(getattr(envs, "step_async", None)) and callable(
                getattr(envs, "step_wait", None)
            )
        self._supports_async = bool(supports)
        self._enabled = bool(enabled)
        self._pending_actions: Any = None
        self._in_flight = False
        self._t_dispatch = 0.0
        self._overlap_s = 0.0
        self._overlap_steps = 0

    @property
    def pipelined(self) -> bool:
        return self._enabled and self._supports_async

    def step_async(self, actions) -> None:
        if self._in_flight:
            raise RuntimeError("step_async called with a step already in flight")
        if self.pipelined:
            self.envs.step_async(actions)
            self._t_dispatch = time.perf_counter()
        else:
            self._pending_actions = actions
        self._in_flight = True

    def step_wait(self):
        if not self._in_flight:
            raise RuntimeError("step_wait called with no step in flight")
        self._in_flight = False
        if self.pipelined:
            # everything the host did since dispatch ran concurrently with the
            # env workers; the env time it covered is what the pipeline hides
            dt = time.perf_counter() - self._t_dispatch
            self._overlap_s += dt
            self._overlap_steps += 1
            _PROCESS_OVERLAP[0] += dt
            _PROCESS_OVERLAP[1] += 1
            return self.envs.step_wait()
        actions, self._pending_actions = self._pending_actions, None
        return self.envs.step(actions)

    def step(self, actions):
        """Blocking convenience (prologue steps outside the pipelined region)."""
        self.step_async(actions)
        return self.step_wait()

    def drain_overlap(self) -> Tuple[float, int]:
        """(overlap seconds, steps) since the last drain — log-boundary friendly."""
        out = (self._overlap_s, self._overlap_steps)
        self._overlap_s, self._overlap_steps = 0.0, 0
        return out

    def __getattr__(self, name: str) -> Any:
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self.envs, name)


class _LeafSpec(NamedTuple):
    shape: Tuple[int, ...]  # raw host shape, leading n_envs included
    kind: str  # "u8" (raw bytes) | "f32" (host-cast float bytes)
    is_cnn: bool
    offset: int  # byte offset into the packed buffer
    nbytes: int


class PackedObsCodec:
    """One-transfer obs (+extras) packing with an in-graph decode.

    ``encode`` serializes every obs leaf — uint8 pixels as raw bytes, everything
    else host-cast to float32 — into a single uint8 buffer and issues ONE
    ``jax.device_put``. ``decode_obs`` is traceable and reproduces the algo's
    ``prepare_obs``/``_normalize`` semantics exactly: cnn keys collapse any
    frame-stack dim into channels and become centered floats
    (``reshape(*leading, -1, H, W) / 255 - 0.5``), mlp keys flatten to
    ``reshape(*leading, -1)`` float32 — so the packed act path is bit-identical
    to the per-key ``device_put`` path (pinned by the packed-parity test).

    ``extra`` leaves (rewards/dones of the previous step) ride the same buffer
    and are decoded UN-normalized by ``decode_extra`` — this is how the rollout
    buffer's env write shares the act path's single transfer.

    The layout is frozen at first encode; ``signature`` is hashable and keys the
    per-codec jit caches (two codecs with equal-length buffers but different
    layouts must not share a trace).
    """

    def __init__(
        self,
        cnn_keys: Sequence[str] = (),
        device: Optional[Any] = None,
        leading_dims: Optional[Tuple[int, ...]] = None,
    ):
        self._cnn_keys = frozenset(cnn_keys)
        self._device = device
        self._leading = tuple(int(d) for d in leading_dims) if leading_dims is not None else None
        self._obs_spec: Optional[Dict[str, _LeafSpec]] = None
        self._extra_spec: Optional[Dict[str, _LeafSpec]] = None
        self._total_bytes = 0
        self._extra_only_offset_delta = 0

    # ----- layout -----------------------------------------------------------------------
    def _freeze(self, obs: Mapping[str, Any], extra: Optional[Mapping[str, Any]]) -> None:
        off = 0
        obs_spec: Dict[str, _LeafSpec] = {}
        for k in sorted(obs):
            arr = np.asarray(obs[k])
            kind = "u8" if arr.dtype == np.uint8 else "f32"
            nbytes = arr.size * (1 if kind == "u8" else 4)
            obs_spec[k] = _LeafSpec(tuple(arr.shape), kind, k in self._cnn_keys, off, nbytes)
            off += nbytes
        self._extra_only_offset_delta = off
        extra_spec: Dict[str, _LeafSpec] = {}
        for k in sorted(extra or {}):
            arr = np.asarray(extra[k])
            nbytes = arr.size * 4
            extra_spec[k] = _LeafSpec(tuple(arr.shape), "f32", False, off, nbytes)
            off += nbytes
        self._obs_spec, self._extra_spec, self._total_bytes = obs_spec, extra_spec, off
        if self._leading is None:
            first = next(iter(obs_spec.values())) if obs_spec else None
            self._leading = (first.shape[0],) if first is not None else (1,)

    @property
    def signature(self) -> Tuple:
        if self._obs_spec is None:
            raise RuntimeError("codec layout not frozen yet: encode at least once")
        return (
            tuple((k, s) for k, s in self._obs_spec.items()),
            tuple((k, s) for k, s in self._extra_spec.items()),
            self._leading,
        )

    @property
    def extra_keys(self) -> Tuple[str, ...]:
        return tuple(self._extra_spec or ())

    # ----- host side: ONE device_put ----------------------------------------------------
    def _leaf_bytes(self, key: str, value: Any, spec: _LeafSpec) -> bytes:
        arr = np.asarray(value)
        if tuple(arr.shape) != spec.shape:
            raise ValueError(
                f"packed leaf '{key}' changed shape: {tuple(arr.shape)} vs frozen {spec.shape}"
            )
        if spec.kind == "u8":
            if arr.dtype != np.uint8:
                raise ValueError(f"packed leaf '{key}' changed dtype: {arr.dtype} vs frozen uint8")
            return arr.tobytes()
        return np.asarray(arr, dtype=np.float32).tobytes()

    def encode(self, obs: Mapping[str, Any], extra: Optional[Mapping[str, Any]] = None) -> jax.Array:
        """Pack obs (+extra float leaves) and issue the step's single ``device_put``."""
        if self._obs_spec is None:
            self._freeze(obs, extra)
        if set(obs) != set(self._obs_spec) or set(extra or {}) != set(self._extra_spec):
            raise ValueError(
                f"packed key set changed: obs {sorted(obs)} extra {sorted(extra or {})} vs "
                f"frozen obs {sorted(self._obs_spec)} extra {sorted(self._extra_spec)}"
            )
        parts = [self._leaf_bytes(k, obs[k], self._obs_spec[k]) for k in self._obs_spec]
        parts += [self._leaf_bytes(k, extra[k], self._extra_spec[k]) for k in self._extra_spec]
        packed = np.frombuffer(b"".join(parts), np.uint8)
        return jax.device_put(packed, self._device)

    def encode_extra_only(self, extra: Mapping[str, Any]) -> jax.Array:
        """Pack ONLY the extra leaves (rollout-flush path: the last step's env
        products have no next act transfer to ride). The buffer is shorter, so
        decode jits retrace on shape — no layout ambiguity."""
        if self._extra_spec is None or not self._extra_spec:
            raise RuntimeError("codec has no extra leaves")
        parts = [self._leaf_bytes(k, extra[k], self._extra_spec[k]) for k in self._extra_spec]
        return jax.device_put(np.frombuffer(b"".join(parts), np.uint8), self._device)

    # ----- device side: traceable decode ------------------------------------------------
    @staticmethod
    def _slice_f32(packed: jax.Array, off: int, nbytes: int) -> jax.Array:
        raw = jax.lax.slice(packed, (off,), (off + nbytes,))
        return jax.lax.bitcast_convert_type(raw.reshape(-1, 4), jnp.float32)

    def decode_obs(self, packed: jax.Array) -> Dict[str, jax.Array]:
        """Traceable unpack + normalize (mirrors ``prepare_obs`` / ``_normalize``)."""
        if self._obs_spec is None:
            raise RuntimeError("codec layout not frozen yet: encode at least once")
        out: Dict[str, jax.Array] = {}
        for k, spec in self._obs_spec.items():
            if spec.kind == "u8":
                raw = jax.lax.slice(packed, (spec.offset,), (spec.offset + spec.nbytes,))
                leaf = raw.reshape(spec.shape).astype(jnp.float32)
            else:
                leaf = self._slice_f32(packed, spec.offset, spec.nbytes).reshape(spec.shape)
            if spec.is_cnn:
                out[k] = leaf.reshape(*self._leading, -1, *spec.shape[-2:]) / 255.0 - 0.5
            else:
                out[k] = leaf.reshape(*self._leading, -1)
        return out

    def decode_obs_raw(self, packed: jax.Array) -> Dict[str, jax.Array]:
        """Traceable unpack WITHOUT normalization: float32 leaves in their raw
        host shapes. The rollout buffer stores RAW obs (train normalizes
        in-graph), so its packed env write uses this instead of decode_obs."""
        if self._obs_spec is None:
            raise RuntimeError("codec layout not frozen yet: encode at least once")
        out: Dict[str, jax.Array] = {}
        for k, spec in self._obs_spec.items():
            if spec.kind == "u8":
                raw = jax.lax.slice(packed, (spec.offset,), (spec.offset + spec.nbytes,))
                out[k] = raw.reshape(spec.shape).astype(jnp.float32)
            else:
                out[k] = self._slice_f32(packed, spec.offset, spec.nbytes).reshape(spec.shape)
        return out

    def decode_extra(self, packed: jax.Array, extra_only: bool = False) -> Dict[str, jax.Array]:
        """Traceable unpack of the extra leaves, raw shapes, no normalization.

        ``extra_only=True`` reads a buffer produced by :meth:`encode_extra_only`
        (offsets shift down by the obs segment's size).
        """
        if self._extra_spec is None:
            raise RuntimeError("codec layout not frozen yet: encode at least once")
        delta = self._extra_only_offset_delta if extra_only else 0
        return {
            k: self._slice_f32(packed, spec.offset - delta, spec.nbytes).reshape(spec.shape)
            for k, spec in self._extra_spec.items()
        }
