"""Collective/compute overlap: microbatched grad accumulation + XLA profiles.

Two halves of ROADMAP item 2's "collective/compute overlap":

- :func:`accumulate_grads` — the Megatron-style bucketed gradient sync,
  expressed in JAX terms: each minibatch splits into ``algo.grad_microbatches``
  chunks inside a ``lax.scan``, and each chunk's gradient all-reduces with its
  own ``jax.lax.psum`` *inside* the loop body. Under ``shard_map`` that gives
  XLA one independent collective per bucket, so the latency-hiding scheduler
  can overlap bucket *i*'s all-reduce with bucket *i+1*'s backward pass instead
  of serializing one monolithic all-reduce behind the whole backward. The
  accumulation math is exact: chunk losses are per-chunk means summed then
  divided by ``m``, and gradients are summed raw then divided once by
  ``m * axis_size`` — for equal power-of-two chunk counts this reproduces the
  single-batch ``value_and_grad`` + ``pmean`` result bit-for-bit on data whose
  sums are exactly representable (pinned by the ``-m mesh`` parity tests).

- :func:`apply_xla_profile` — the ``fabric.xla_profile`` knob. Where
  ``JAX_PLATFORMS`` names a TPU it appends the latency-hiding-scheduler /
  async-collective-fusion flag set to ``LIBTPU_INIT_ARGS`` (idempotently, and
  only for flags the caller has not already pinned); on CPU it is a structural
  no-op. The flags are libtpu's, not XLA's: in ``XLA_FLAGS`` they are a FATAL
  "Unknown flag" at backend start-up (checked on a TPU v5 lite with libtpu
  0.0.34, PR 22). Either way the active profile is stamped into every
  subsequent compiled-program ledger row via
  :func:`sheeprl_tpu.telemetry.programs.set_context`, so the HLO collective
  audit in a row is always joinable with the scheduling profile it compiled
  under. libtpu reads the variable at backend initialization, which is why
  :class:`~sheeprl_tpu.core.runtime.Runtime` applies the profile from its
  ``__post_init__`` — before the first compile on that runtime.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

#: TPU overlap-scheduling flag set (see /opt/skills guidance + GSPMD/PaLM
#: recipes): latency-hiding scheduler to move collective starts early, async
#: collective fusion so all-reduce/all-gather compile as start/done pairs the
#: scheduler can actually move.
_PROFILE_FLAGS = {
    "overlap": (
        "--xla_tpu_enable_latency_hiding_scheduler=true",
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
        "--xla_tpu_overlap_compute_collective_tc=true",
    ),
}

_FLAGS_ENV = "LIBTPU_INIT_ARGS"
_logger = logging.getLogger("sheeprl_tpu.overlap")


def known_profiles() -> Tuple[str, ...]:
    return tuple(sorted(_PROFILE_FLAGS))


def _platform_hint() -> str:
    """The platform *before* backend init: the env var / jax config, NOT
    jax.devices() (which would initialize the backend and freeze the flags —
    exactly what this module must run ahead of). Empty when JAX is left to
    auto-detect."""
    return (jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "") or "").lower()


def apply_xla_profile(profile: Optional[str]) -> bool:
    """Activate ``fabric.xla_profile``. Returns True when the flag set was
    actually appended to ``LIBTPU_INIT_ARGS`` (``JAX_PLATFORMS`` names a TPU),
    False for the record-only path (CPU, no platform named, or no profile).
    Always stamps the profile into the program-ledger context so rows say what
    they ran under."""
    from sheeprl_tpu.telemetry import programs as tel_programs

    if not profile:
        return False
    profile = str(profile)
    flags = _PROFILE_FLAGS.get(profile)
    if flags is None:
        raise ValueError(
            f"unknown fabric.xla_profile {profile!r}; known: {', '.join(known_profiles())}"
        )
    tel_programs.set_context(xla_profile=profile)
    hint = _platform_hint()
    if "tpu" not in hint:
        # CPU/GPU hosts: there is no latency-hiding scheduler to drive. The
        # ledger context still records the requested profile (acceptance
        # evidence on the virtual mesh), making this a structural no-op, not a
        # silent one. With NO platform named, a TPU found by auto-detection
        # would go without its flags: say so.
        if not hint:
            _logger.warning(
                "fabric.xla_profile=%s not applied: JAX_PLATFORMS names no platform, and "
                "the backend cannot be probed before its flags are set; export "
                "JAX_PLATFORMS=tpu,cpu on a TPU host",
                profile,
            )
        return False
    current = os.environ.get(_FLAGS_ENV, "")
    have = {f.split("=", 1)[0] for f in current.split() if f}
    added = [f for f in flags if f.split("=", 1)[0] not in have]
    if added:
        os.environ[_FLAGS_ENV] = " ".join(([current] if current else []) + added)
    return True


def microbatches(cfg: Any) -> int:
    """Resolve ``algo.grad_microbatches`` (missing/None/0 -> 1)."""
    try:
        m = cfg.algo.get("grad_microbatches", 1)
    except AttributeError:
        m = getattr(getattr(cfg, "algo", None), "grad_microbatches", 1)
    return max(int(m or 1), 1)


def accumulate_grads(
    grad_fn: Callable[..., Tuple[Tuple[Any, Any], Any]],
    params: Any,
    batch: Any,
    loss_args: Sequence[Any] = (),
    *,
    microbatches: int,
    axis_name: Optional[str] = None,
    axis_size: int = 1,
) -> Tuple[Tuple[Any, Any], Any]:
    """Microbatched replacement for ``grad_fn(params, batch, *loss_args)``.

    ``grad_fn`` must be a ``jax.value_and_grad(..., has_aux=True)`` of a loss
    that is a *mean* over the batch axis (axis 0 of every ``batch`` leaf).
    The batch splits into ``microbatches`` equal chunks; a ``lax.scan`` runs
    the backward per chunk and — when ``axis_name`` is given — all-reduces
    each chunk's gradient with its own in-loop ``psum`` (the per-bucket
    collective the latency-hiding scheduler overlaps with the next chunk's
    backward). Returns ``((loss, aux), grads)`` shaped exactly like the
    single-batch call, with one contract shift: when ``axis_name`` is set the
    returned ``grads`` are ALREADY averaged across the axis (callers must
    skip their own ``pmean(grads)``); the scalar ``loss``/``aux`` are local
    chunk-averages, left for the caller's existing scalar reductions.
    """
    m = int(microbatches)
    if m <= 1:
        (loss, aux), grads = grad_fn(params, batch, *loss_args)
        if axis_name is not None:
            grads = jax.lax.pmean(grads, axis_name)
        return (loss, aux), grads

    def _chunk(x: Any) -> Any:
        x = jnp.asarray(x)
        bs = x.shape[0] if x.ndim else 0
        if bs % m:
            raise ValueError(
                f"algo.grad_microbatches={m} must divide the per-shard minibatch "
                f"size, got a leaf with batch dim {bs}"
            )
        return x.reshape((m, bs // m) + x.shape[1:])

    chunks = jax.tree_util.tree_map(_chunk, batch)
    first = jax.tree_util.tree_map(lambda x: x[0], chunks)
    out_sds = jax.eval_shape(lambda p, b: grad_fn(p, b, *loss_args), params, first)
    zeros = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), out_sds)
    (loss0, aux0), grads0 = zeros

    def body(carry, chunk):
        loss_acc, aux_acc, grads_acc = carry
        (loss, aux), grads = grad_fn(params, chunk, *loss_args)
        if axis_name is not None:
            # per-bucket all-reduce INSIDE the scan: one independent collective
            # per chunk, issued as soon as this chunk's backward finishes
            grads = jax.lax.psum(grads, axis_name)
        grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
        loss_acc = jax.tree_util.tree_map(jnp.add, loss_acc, loss)
        aux_acc = jax.tree_util.tree_map(jnp.add, aux_acc, aux)
        return (loss_acc, aux_acc, grads_acc), None

    (loss_sum, aux_sum, grads_sum), _ = jax.lax.scan(body, (loss0, aux0, grads0), chunks)
    # one exact division at the end: psum'd chunk grads / (m * axis_size) ==
    # pmean of the full-batch grad; chunk-mean losses / m == full-batch mean
    gdiv = float(m * (axis_size if axis_name is not None else 1))
    grads = jax.tree_util.tree_map(lambda g: g / gdiv, grads_sum)
    loss = jax.tree_util.tree_map(lambda v: v / m, loss_sum)
    aux = jax.tree_util.tree_map(lambda v: v / m, aux_sum)
    return (loss, aux), grads
