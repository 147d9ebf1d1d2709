"""HBM-resident on-policy rollout buffer: the ``[T, B, *]`` rollout never ping-pongs.

On-policy counterpart of ``device_buffer.py`` (the off-policy HBM replay). The
host-numpy rollout design (``algos/ppo/ppo.py`` reference loop) pulls
``values``/``logprobs``/``actions`` back to host with ``np.asarray`` on EVERY
env step — a blocking device->host sync that defeats JAX async dispatch — only
to re-upload the whole ``[T, B]`` rollout to the trainer each iteration. Here
the rollout stays resident on the player device:

- policy outputs (``actions``, ``logprobs``, ``values``, recurrent states):
  written at the current row by a donated jitted scatter DIRECTLY from the
  player step's device outputs — they never touch the host (:meth:`add_policy`);
- env products (``obs``, ``rewards``, ``dones``): serialized host-side into ONE
  packed ``jax.device_put`` per step (the same 8-put -> 1-transfer fusion as
  ``device_buffer.py``: every transfer carries a fixed cost) and unpacked +
  scattered in-graph (:meth:`add_env`);
- at iteration end :meth:`rollout` hands the completed ``[T, B, *]`` arrays to
  the jitted train fn with zero bulk host->device transfer. Under the decoupled
  runtime the storage lives on the player CHIP, so the handoff is a direct
  player-chip -> trainer-mesh ``device_put``.

The only per-step device->host sync left in the hot loop is the unavoidable one:
the env-facing actions.

Donation safety: every in-place write donates the storage, so :meth:`rollout`
TRANSFERS OWNERSHIP — the buffer drops its references and the next iteration
allocates fresh storage. The consumer's arrays are therefore never aliased by a
later donated write (no use-after-donate by construction); the transient cost is
one rollout-sized ``jnp.zeros`` per iteration, dispatched asynchronously.

Every leaf is stored float32 — bit-identical to the host path's
``rb.to_arrays(dtype=np.float32)`` handoff, which the backend-parity test pins.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DeviceRolloutBuffer"]


class _LeafMeta(NamedTuple):
    feat: Tuple[int, ...]  # per-step feature shape (leaf.shape[1:])
    flat: int  # prod(feat)


class DeviceRolloutBuffer:
    """Device-resident ``[rollout_steps, n_envs, *feat]`` on-policy rollout.

    One row per env step; :meth:`add_policy` and :meth:`add_env` both write at
    the current row and :meth:`add_env` closes it (the loops always write the
    policy half first, then step the env). Writing past ``rollout_steps`` rows
    or reading an incomplete rollout raises — on-policy data is consumed exactly
    once per iteration, silent wraparound would corrupt GAE.
    """

    backend = "device"

    def __init__(self, rollout_steps: int, n_envs: int, device: Optional[Any] = None):
        if rollout_steps <= 0:
            raise ValueError(f"a rollout buffer needs a positive length; received rollout_steps={rollout_steps}")
        if n_envs <= 0:
            raise ValueError(f"a rollout buffer needs at least one env stream; received n_envs={n_envs}")
        self._T = int(rollout_steps)
        self._B = int(n_envs)
        self._device = device
        self._buf: Optional[Dict[str, jax.Array]] = None
        self._meta: Dict[str, _LeafMeta] = {}
        self._t = 0  # host-side write cursor (rows fully written)
        # device-resident mirror of the cursor: the policy write's row index must
        # ride as a DEVICE scalar (a host np.int32 arg is an implicit per-step
        # host->device transfer — it trips jax.transfer_guard and costs a
        # dispatch on remote transports); env writes return it incremented
        self._t_dev: Optional[jax.Array] = None
        # jit caches keyed by the write's key signature: one compile per key set
        self._policy_write_fns: Dict[Any, Any] = {}
        self._env_write_fns: Dict[Any, Any] = {}
        self._packed_env_write_fns: Dict[Any, Any] = {}

    # ----- properties -------------------------------------------------------------------
    @property
    def rollout_steps(self) -> int:
        return self._T

    @property
    def n_envs(self) -> int:
        return self._B

    @property
    def step(self) -> int:
        """Rows written so far (== rollout_steps when the rollout is complete)."""
        return self._t

    @property
    def full(self) -> bool:
        return self._t >= self._T

    @property
    def is_memmap(self) -> bool:
        return False

    def __len__(self) -> int:
        return self._T

    # ----- allocation -------------------------------------------------------------------
    def _alloc_leaf(self, key: str, feat: Tuple[int, ...]) -> None:
        self._meta[key] = _LeafMeta(tuple(int(d) for d in feat), int(np.prod(feat)) if feat else 1)
        shape = (self._T, self._B, *self._meta[key].feat)
        self._buf[key] = jax.jit(
            partial(jnp.zeros, shape, jnp.float32),
            out_shardings=None if self._device is None else jax.sharding.SingleDeviceSharding(self._device),
        )()

    def _ensure(self, data: Dict[str, Any]) -> None:
        if self._buf is None:
            self._buf = {}
        for k, v in data.items():
            if k in self._meta and k in self._buf:
                continue
            shape = tuple(np.shape(v))
            if not shape or shape[0] != self._B:
                raise ValueError(
                    f"rollout leaf '{k}' must be [n_envs={self._B}, *feat]; got shape {shape}"
                )
            if k in self._meta:  # re-allocation after a rollout() handoff
                if tuple(shape[1:]) != self._meta[k].feat:
                    raise ValueError(
                        f"rollout leaf '{k}' changed shape: {tuple(shape[1:])} vs {self._meta[k].feat}"
                    )
                full_shape = (self._T, self._B, *self._meta[k].feat)
                self._buf[k] = jax.jit(
                    partial(jnp.zeros, full_shape, jnp.float32),
                    out_shardings=None
                    if self._device is None
                    else jax.sharding.SingleDeviceSharding(self._device),
                )()
            else:
                self._alloc_leaf(k, shape[1:])

    def _check_open_row(self) -> None:
        if self._t >= self._T:
            raise RuntimeError(
                f"rollout buffer is full ({self._T} rows): call rollout() (or reset()) "
                "before writing the next iteration's steps"
            )

    def _cursor(self) -> jax.Array:
        """Device-resident row index: ONE explicit put per iteration (when the
        cursor is first needed after an alloc/reset), then device-only."""
        if self._t_dev is None:
            self._t_dev = jax.device_put(np.int32(self._t), self._device)
        return self._t_dev

    # ----- policy write path (device -> device, in-graph) -------------------------------
    def _policy_write_fn(self, keys_sig):
        if keys_sig not in self._policy_write_fns:

            def write(buf, t, vals):
                return {
                    k: jax.lax.dynamic_update_slice_in_dim(
                        buf[k], vals[k].astype(jnp.float32)[None], t, axis=0
                    )
                    for k in buf
                }

            self._policy_write_fns[keys_sig] = jax.jit(write, donate_argnums=(0,))
        return self._policy_write_fns[keys_sig]

    def add_policy(self, outputs: Dict[str, jax.Array]) -> None:
        """Scatter on-device policy outputs ``[n_envs, *feat]`` at the current row.

        The inputs are the player jit's outputs — already on the buffer's device —
        and the scatter is a donated jitted ``dynamic_update_slice``: no host
        round-trip, no transfer, in-place in HBM. The row index rides as a traced
        DEVICE int32 scalar (one compile for every step, zero per-step transfers).
        """
        self._check_open_row()
        self._ensure(outputs)
        keys_sig = tuple(sorted(outputs))
        sub = {k: self._buf[k] for k in keys_sig}
        out = self._policy_write_fn(keys_sig)(sub, self._cursor(), {k: outputs[k] for k in keys_sig})
        self._buf.update(out)

    # ----- env write path (host -> device, ONE packed transfer) -------------------------
    def _pack(self, data: Dict[str, np.ndarray]) -> np.ndarray:
        """Serialize the row index + every leaf (as float32) into one byte buffer."""
        parts = [np.int32(self._t).tobytes()]
        for key in sorted(data):
            leaf = np.ascontiguousarray(np.asarray(data[key], dtype=np.float32))
            parts.append(leaf.tobytes())
        return np.frombuffer(b"".join(parts), np.uint8)

    def _env_write_fn(self, keys_sig):
        if keys_sig not in self._env_write_fns:
            B = self._B
            metas = {key: self._meta[key] for key in keys_sig}

            def write(buf, packed):
                off = 0

                def take(nbytes):
                    nonlocal off
                    seg = jax.lax.slice(packed, (off,), (off + nbytes,))
                    off += nbytes
                    return seg

                def decode_f32(nelem, shape):
                    raw = take(nelem * 4)
                    return jax.lax.bitcast_convert_type(raw.reshape(-1, 4), jnp.float32).reshape(shape)

                t_raw = take(4)
                t = jax.lax.bitcast_convert_type(t_raw, jnp.int32).reshape(())
                rows = {
                    key: decode_f32(B * metas[key].flat, (1, B, *metas[key].feat)) for key in keys_sig
                }
                written = {
                    key: jax.lax.dynamic_update_slice_in_dim(buf[key], rows[key], t, axis=0)
                    for key in buf
                }
                return written, t + 1  # incremented cursor stays device-resident

            self._env_write_fns[keys_sig] = jax.jit(write, donate_argnums=(0,))
        return self._env_write_fns[keys_sig]

    def add_env(self, data: Dict[str, np.ndarray]) -> None:
        """Write host env products ``[n_envs, *feat]`` at the current row; close it.

        All leaves ride ONE ``jax.device_put`` of a packed uint8 buffer (index
        included), decoded and scattered by a donated jit — the fixed per-transfer
        cost is paid once per step, not per key.
        """
        self._check_open_row()
        self._ensure(data)
        keys_sig = tuple(sorted(data))
        for k in keys_sig:
            shape = tuple(np.shape(data[k]))
            if shape != (self._B, *self._meta[k].feat):
                raise ValueError(
                    f"rollout leaf '{k}' must be [{self._B}, *{self._meta[k].feat}]; got {shape}"
                )
        sub = {k: self._buf[k] for k in keys_sig}
        packed = jax.device_put(self._pack({k: data[k] for k in keys_sig}), self._device)
        out, self._t_dev = self._env_write_fn(keys_sig)(sub, packed)
        self._buf.update(out)
        self._t += 1

    # ----- env write path from codec-packed transfers (ZERO extra transfers) ------------
    def _ensure_from_codec(self, codec) -> None:
        obs_sig, extra_sig, _ = codec.signature
        for k, spec in (*obs_sig, *extra_sig):
            if k in self._meta and k in (self._buf or {}):
                continue
            if spec.shape[0] != self._B:
                raise ValueError(
                    f"packed rollout leaf '{k}' must be [n_envs={self._B}, *feat]; got {spec.shape}"
                )
            if self._buf is None:
                self._buf = {}
            if k in self._meta:  # re-allocation after a rollout() handoff
                full_shape = (self._T, self._B, *self._meta[k].feat)
                self._buf[k] = jax.jit(
                    partial(jnp.zeros, full_shape, jnp.float32),
                    out_shardings=None
                    if self._device is None
                    else jax.sharding.SingleDeviceSharding(self._device),
                )()
            else:
                self._alloc_leaf(k, spec.shape[1:])

    def _packed_env_write_fn(self, codec, extra_only: bool):
        sig = (id(codec), bool(extra_only), codec.signature)
        if sig not in self._packed_env_write_fns:

            def write(buf, t, obs_packed, extra_packed):
                rows = dict(codec.decode_obs_raw(obs_packed))
                rows.update(codec.decode_extra(extra_packed, extra_only=extra_only))
                return {
                    key: jax.lax.dynamic_update_slice_in_dim(buf[key], rows[key][None], t, axis=0)
                    for key in buf
                }, t + 1

            self._packed_env_write_fns[sig] = jax.jit(write, donate_argnums=(0,))
        return self._packed_env_write_fns[sig]

    def add_env_packed(self, codec, obs_packed: jax.Array, extra_packed: jax.Array, extra_only: bool = False) -> None:
        """Close the current row from codec-packed buffers ALREADY on device.

        The pipelined loops transfer each step's obs once, for the act dispatch
        (``PackedObsCodec.encode`` with the previous step's rewards/dones riding
        as extra leaves); this write re-reads that same device buffer — obs from
        the PREVIOUS step's put, rewards/dones from the current one — so closing
        a row costs zero additional host->device transfers. ``extra_only=True``
        is the end-of-rollout flush, where the last step's env products arrive
        in a short ``encode_extra_only`` buffer instead.
        """
        self._check_open_row()
        self._ensure_from_codec(codec)
        obs_sig, extra_sig, _ = codec.signature
        keys = tuple(k for k, _ in (*obs_sig, *extra_sig))
        sub = {k: self._buf[k] for k in keys}
        out, self._t_dev = self._packed_env_write_fn(codec, extra_only)(
            sub, self._cursor(), obs_packed, extra_packed
        )
        self._buf.update(out)
        self._t += 1

    # ----- handoff ----------------------------------------------------------------------
    def rollout(self) -> Dict[str, jax.Array]:
        """The completed ``{key: [T, B, *feat]}`` rollout ON the buffer's device.

        Ownership transfers to the caller: the buffer forgets its storage (the
        next iteration allocates fresh zeros), so later donated writes can never
        alias arrays the train fn still holds.
        """
        if self._t != self._T:
            raise RuntimeError(
                f"incomplete rollout: {self._t}/{self._T} rows written; on-policy data "
                "is consumed once per full rollout"
            )
        if self._buf is None:  # T rows counted but nothing ever written
            raise RuntimeError("empty rollout buffer")
        out, self._buf, self._t, self._t_dev = self._buf, None, 0, None
        return out

    def rollout_host(self) -> Dict[str, np.ndarray]:
        """Host-numpy copy of the completed rollout (one bulk device->host pull).

        For consumers that need host data once per iteration: the recurrent
        loop's episode chunking, the cross-host decoupled broadcast, metric
        logging of values/rewards, and checkpointing (the de-layout contract of
        ``DeviceSequentialReplayBuffer._logical_to_host``).
        """
        return {k: np.asarray(jax.device_get(v)) for k, v in self.rollout().items()}

    def reset(self) -> None:
        """Drop any partial rollout (crash-restart / resume path)."""
        self._buf = None
        self._t = 0
        self._t_dev = None

    # ----- checkpointing ----------------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        """De-layouted host state (same contract as the HBM replay's checkpoint
        path: arrays leave the device as plain numpy, so checkpoints stay
        device-agnostic). On-policy rollouts are normally consumed before a
        checkpoint fires, so this is typically ``{"rollout": None, "t": 0}``."""
        host = (
            {k: np.asarray(jax.device_get(v)) for k, v in self._buf.items()}
            if self._buf is not None
            else None
        )
        return {"rollout": host, "t": int(self._t)}

    def load_state_dict(self, state: Dict[str, Any]) -> "DeviceRolloutBuffer":
        if "rollout" not in state:
            raise ValueError("Unrecognized rollout-buffer checkpoint payload")
        self.reset()
        host = state["rollout"]
        if host:
            first = next(iter(host.values()))
            if tuple(np.shape(first)[:2]) != (self._T, self._B):
                raise ValueError(
                    f"Checkpointed rollout is {tuple(np.shape(first)[:2])} but this run is "
                    f"configured for [{self._T} x {self._B} envs]"
                )
            self._buf = {}
            self._meta = {}
            self._policy_write_fns, self._env_write_fns, self._packed_env_write_fns = {}, {}, {}
            for k, v in host.items():
                arr = np.asarray(v, dtype=np.float32)
                self._alloc_leaf(k, arr.shape[2:])
                self._buf[k] = jax.device_put(arr, self._device)
        self._t = int(state["t"])
        return self
