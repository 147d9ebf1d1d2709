"""Double-buffered host->HBM prefetch for replay-buffer sampling.

TPU-native counterpart of the reference's ``sample_tensors(..., device=device,
non_blocking=True)`` pinned-memory path (reference sheeprl/data/buffers.py:290-326):
instead of pinned host staging, a worker thread runs the (numpy) sample and starts the
asynchronous ``jax.device_put`` while the accelerator is still busy with the *previous*
train step, so host gather + host->device transfer overlap compute instead of
serializing with it.

Semantics note: the speculative batch for iteration ``t+1`` is sampled at the end of
iteration ``t``, i.e. before the env steps taken between the two iterations land in
the buffer. For off-policy replay at real buffer sizes this lag of one transition
batch is statistically irrelevant (the reference's decoupled trainers sample from a
snapshot that is older still). Whenever the requested sample kwargs change (e.g. the
Ratio scheduler yields a different ``n_samples``), the stale speculation is discarded
and the sample runs synchronously — results are always shape-correct.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from sheeprl_tpu.data.buffers import get_array
from sheeprl_tpu.telemetry import trace

__all__ = ["DevicePrefetcher", "InlineSampler"]


class InlineSampler:
    """Prefetcher-shaped shim for buffers whose sampling is already on-device
    (``DeviceSequentialReplayBuffer``): ``get`` just samples — there is no host
    gather or transfer to overlap — while ``guard``/``close`` keep the train
    loops' locking structure uniform."""

    def __init__(self, sample_fn: Callable[..., Dict[str, Any]]):
        self._sample_fn = sample_fn
        self._lock = threading.Lock()

    def get(self, **kwargs) -> Dict[str, Any]:
        return self._sample_fn(**kwargs)

    def guard(self) -> threading.Lock:
        return self._lock

    def close(self) -> None:
        pass

    def __enter__(self) -> "InlineSampler":
        return self

    def __exit__(self, *exc) -> None:
        pass


class DevicePrefetcher:
    """Overlap ``sample_fn(**kwargs)`` + device transfer with accelerator compute.

    Args:
        sample_fn: returns a dict of numpy arrays (e.g. ``buffer.sample``).
        device: a ``jax.Device`` or ``jax.sharding.Sharding`` the batch lands on.
            ``None`` keeps arrays on host (still overlaps the host-side gather).
        dtype: optional dtype override forwarded to :func:`get_array` per leaf.

    Usage (the train loop calls ``get`` once per iteration)::

        pf = DevicePrefetcher(rb.sample, device=sharding)
        ...
        batch = pf.get(batch_size=bs, sequence_length=T, n_samples=g)  # device tree
        train_fn(..., batch, ...)

    ``get`` consumes the speculative batch when its kwargs match the request
    (the common steady-state), otherwise samples synchronously; either way it
    immediately begins speculating the next batch with the same kwargs.
    """

    def __init__(
        self,
        sample_fn: Callable[..., Dict[str, np.ndarray]],
        device: Optional[Any] = None,
        dtype: Optional[Any] = None,
        io_lock: Optional[threading.Lock] = None,
        chunk: int = 1,
        chunk_key: Optional[str] = None,
    ):
        self._sample_fn = sample_fn
        self._device = device
        self._dtype = dtype
        # Transfer amortization: when ``chunk > 1`` and a get() request carries the
        # integer kwarg named ``chunk_key`` (the per-call batch count, e.g.
        # ``n_samples`` for sequential replay or ``g`` for flat replay), the worker
        # samples ``chunk`` calls' worth in ONE sample_fn call / ONE device transfer
        # and get() serves device-side slices of it. Each transfer's completion
        # fence is a synchronous host<->device round trip, so K-way chunking
        # divides that latency by K. Replay-semantics cost: piece i of a chunk was
        # sampled i train-calls early (up to chunk-1 calls of staleness) — for
        # off-policy replay at real buffer sizes this is statistically irrelevant
        # (see the module docstring's one-batch-lag argument; the lag here is K, not 1).
        self._chunk = max(1, int(chunk))
        self._chunk_key = chunk_key
        self._pieces: list = []
        self._pieces_kwargs: Optional[Dict[str, Any]] = None
        self._slice_fns: Dict[Any, Any] = {}
        # Serializes buffer access: the worker's sample vs. the train loop's add
        # (torn-row reads once the circular write head wraps into the sampled
        # region) and, with a shared lock, concurrent samples from several
        # prefetchers racing one np.random.Generator. Train loops wrap their
        # ``rb.add`` in ``with prefetcher.guard():``.
        self._io_lock = io_lock or threading.Lock()
        self._cond = threading.Condition()
        # job state, all guarded by _cond: a monotonically increasing job id tags
        # results so a stale (discarded) speculation can never satisfy a newer get()
        self._job_id = 0
        self._job_kwargs: Optional[Dict[str, Any]] = None
        self._job_parent = ""
        self._done_id = 0
        self._result: Optional[Dict[str, Any]] = None
        self._error: Optional[BaseException] = None
        self._closed = False
        self._worker = threading.Thread(target=self._run, name="sheeprl-prefetch", daemon=True)
        self._worker.start()

    # Batches below this stay unfenced: the fence costs one synchronous host<->device
    # round trip, and small-batch staging residue is bounded by iteration count,
    # not worth a per-iteration sync.
    FENCE_BYTES = 4 * 1024 * 1024

    # ----- worker --------------------------------------------------------------------
    def _transfer(self, batch: Dict[str, np.ndarray], parent_id: Optional[str]) -> Dict[str, Any]:
        # device_put returns immediately; the async copy completes while the
        # consumer is still dispatching/awaiting the previous train step.
        total_bytes = sum(getattr(v, "nbytes", 0) for v in batch.values())
        with trace.span("prefetch.h2d", parent_id=parent_id, bytes=total_bytes):
            out = {k: get_array(v, dtype=self._dtype, device=self._device) for k, v in batch.items()}
        if self._device is not None and out and total_bytes >= self.FENCE_BYTES:
            # Fence: block THIS worker thread until the batch is device-resident,
            # bounding in-flight transfers to the double-buffer depth. Without it
            # the consumer outruns the copies and the host transfer queue grows
            # without bound. The fence is a real host pull of a probe that depends
            # on every leaf, so ONE round trip fences them all.
            import jax.numpy as jnp

            with trace.span("prefetch.h2d_fence", parent_id=parent_id):
                probe = jnp.stack([v[(0,) * v.ndim].astype(jnp.float32) for v in out.values()])
                np.asarray(jax.device_get(probe))
        return out

    def _sample(self, kwargs: Dict[str, Any], parent_id: Optional[str] = None) -> Dict[str, np.ndarray]:
        """``sample_fn`` under the IO lock. ``parent_id`` is the ``prefetch.get``
        span that launched the job when this runs on the worker thread: its
        spans hang under that span across the threads."""
        with self._io_lock:
            with trace.span("prefetch.sample", parent_id=parent_id, n_samples=kwargs.get("n_samples")):
                return self._sample_fn(**kwargs)

    def _run(self) -> None:
        while True:
            with self._cond:
                # _job_kwargs is None marks a cancelled slot (kwargs mismatch in get):
                # the id was bumped so a stale publish is impossible, but there is
                # nothing to compute until the next _launch_locked.
                while (self._job_id == self._done_id or self._job_kwargs is None) and not self._closed:
                    self._cond.wait()
                if self._closed:
                    return
                job_id, kwargs, parent_id = self._job_id, dict(self._job_kwargs or {}), self._job_parent
            try:
                # ``batch`` is this loop's own name on purpose: the last chunk's host arrays
                # (50 MB at DV3-XL) are released when it is bound anew, while the train loop
                # sits in its fence. Released right after the transfer's fence, which ends
                # when the train step does, the unmapping falls on the loop's next dispatch
                # (measured on the v5e: +4 ms on every fourth step, PERF.md PR 27).
                batch = self._sample(kwargs, parent_id)
                result: Tuple[Optional[Dict[str, Any]], Optional[BaseException]] = (
                    self._transfer(batch, parent_id),
                    None,
                )
            except BaseException as e:  # surfaced on the consumer thread in get()
                result = (None, e)
            with self._cond:
                # a newer job may have been launched meanwhile; only publish if current
                if job_id == self._job_id:
                    self._result, self._error = result
                    self._done_id = job_id
                    self._cond.notify_all()

    # ----- consumer ------------------------------------------------------------------
    def _launch_locked(self, kwargs: Dict[str, Any]) -> None:
        self._job_id += 1
        self._job_kwargs = dict(kwargs)
        self._job_parent = trace.current_span_id()  # the worker's spans hang under the get() that launched them
        self._result = None
        self._error = None
        self._cond.notify_all()

    def _chunkable(self, kwargs: Dict[str, Any]) -> bool:
        return (
            self._chunk > 1
            and self._chunk_key is not None
            and isinstance(kwargs.get(self._chunk_key), (int, np.integer))
            and int(kwargs[self._chunk_key]) > 0
        )

    def _scaled(self, kwargs: Dict[str, Any]) -> Dict[str, Any]:
        out = dict(kwargs)
        out[self._chunk_key] = int(kwargs[self._chunk_key]) * self._chunk
        return out

    def _slice_pieces(self, superbatch: Dict[str, Any], kwargs: Dict[str, Any]) -> list:
        """Split one transferred superbatch into ``chunk`` device-side pieces.

        All slices happen in ONE jitted call (cached per shape): eager per-leaf
        slicing would dispatch a separate device op per leaf per piece, and on
        remote backends every dispatched op carries fixed execution overhead that
        would eat the latency the chunking just saved. Host mode (device=None)
        keeps the documented numpy passthrough: plain views, no jit."""
        g = int(kwargs[self._chunk_key])
        if self._device is None:
            return [
                jax.tree_util.tree_map(lambda v, i=i: v[i * g : (i + 1) * g], superbatch)
                for i in range(self._chunk)
            ]
        key = (g, self._chunk)
        fn = self._slice_fns.get(key)
        if fn is None:

            def split(tree):
                return [
                    jax.tree_util.tree_map(lambda v: jax.lax.slice_in_dim(v, i * g, (i + 1) * g, axis=0), tree)
                    for i in range(self._chunk)
                ]

            fn = self._slice_fns[key] = jax.jit(split)
        return fn(superbatch)

    def get(self, **kwargs) -> Dict[str, Any]:
        """Return a (device-resident) batch for ``kwargs``; speculate the next one."""
        # served: "piece" of a transferred chunk, a "speculated" batch (waited for
        # if need be), or sampled in line ("sync": first call, or the kwargs changed)
        with trace.span("prefetch.get") as sp:
            if self._chunkable(kwargs):
                return self._get_chunked(kwargs, sp)
            return self._get_single(kwargs, sp)

    def _get_single(self, kwargs: Dict[str, Any], sp: Any) -> Dict[str, Any]:
        with self._cond:
            if self._closed:
                raise RuntimeError("DevicePrefetcher is closed")
            speculated = self._job_id > 0 and self._job_kwargs == kwargs
            if speculated:
                while self._done_id != self._job_id and not self._closed:
                    self._cond.wait()
                if self._closed:
                    raise RuntimeError("DevicePrefetcher closed while waiting for a batch")
                result, err = self._result, self._error
                self._launch_locked(kwargs)
            else:
                # mismatch (or first call): bump the job id so an in-flight stale
                # speculation can never publish, then sample synchronously below
                self._job_id += 1
                self._job_kwargs = None
        if not speculated:
            sp.set(served="sync")
            return self._sample_now(kwargs, kwargs)
        sp.set(served="speculated")
        if err is not None:
            raise err
        return result

    def _sample_now(self, kwargs: Dict[str, Any], speculate_kwargs: Dict[str, Any]) -> Dict[str, Any]:
        """Sample+transfer synchronously on the consumer thread, then speculate
        ``speculate_kwargs`` (the scaled kwargs in chunked mode)."""
        try:
            result, err = self._transfer(self._sample(kwargs), None), None
        except BaseException as e:
            result, err = None, e
        with self._cond:
            if not self._closed:
                self._launch_locked(speculate_kwargs)
        if err is not None:
            raise err
        return result

    def _get_chunked(self, kwargs: Dict[str, Any], sp: Any) -> Dict[str, Any]:
        scaled = self._scaled(kwargs)
        with self._cond:
            if self._closed:
                raise RuntimeError("DevicePrefetcher is closed")
            # steady state: serve a ready piece of the current superbatch
            if self._pieces and self._pieces_kwargs == kwargs:
                sp.set(served="piece")
                return self._pieces.pop(0)
            speculated = self._job_id > 0 and self._job_kwargs == scaled
            if speculated:
                sp.set(served="speculated")
                while self._done_id != self._job_id and not self._closed:
                    self._cond.wait()
                if self._closed:
                    raise RuntimeError("DevicePrefetcher closed while waiting for a batch")
                superbatch, err = self._result, self._error
                if err is None:
                    self._pieces = self._slice_pieces(superbatch, kwargs)
                    self._pieces_kwargs = dict(kwargs)
                    piece = self._pieces.pop(0)
                # next superbatch transfers while the remaining pieces are consumed
                self._launch_locked(scaled)
                if err is not None:
                    raise err
                return piece
            # kwargs changed (or first call): drop stale pieces, cancel the stale
            # speculation, serve ONE unscaled batch synchronously, speculate scaled
            self._pieces = []
            self._pieces_kwargs = None
            self._job_id += 1
            self._job_kwargs = None
        sp.set(served="sync")
        return self._sample_now(kwargs, scaled)

    def guard(self) -> threading.Lock:
        """The IO lock, for the train loop's buffer writes: ``with pf.guard(): rb.add(...)``."""
        return self._io_lock

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._worker.join(timeout=5)

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
