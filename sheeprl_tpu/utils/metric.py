"""Metric aggregation (torchmetrics-free).

Reference: sheeprl/utils/metric.py:17-195 (MetricAggregator + RankIndependent variant).
Metrics here are small host-side accumulators fed with Python floats / numpy / jax
scalars; device->host transfer happens once per log interval, not per step.
"""

from __future__ import annotations

import math
from collections import deque
from typing import Any, Dict, Iterable, Mapping, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from sheeprl_tpu.core import compile as jax_compile


def _to_float(value) -> float:
    if isinstance(value, (int, float)):
        return float(value)
    arr = np.asarray(value)
    return float(arr.mean()) if arr.size > 1 else float(arr)


class EWMAStat:
    """Exponentially weighted running mean/variance with z-scores.

    Host-side scalar statistics for the health sentinel's divergence and stall
    detectors (``core/health.py``): O(1) memory, O(1) update, no window buffer.
    ``window`` sets the smoothing as ``alpha = 2 / (window + 1)`` (the classic
    EWMA span), so ``window=64`` weights roughly the last 64 samples. Variance
    uses the exponentially weighted recurrence
    ``var <- (1 - a) * (var + a * delta^2)`` (West 1979), which is exact for
    the EW moments and never goes negative.
    """

    def __init__(self, window: int = 64):
        self.window = max(int(window), 2)
        self.alpha = 2.0 / (self.window + 1.0)
        self.count = 0
        self.mean = 0.0
        self.var = 0.0

    def update(self, value: float) -> None:
        v = float(value)
        if not math.isfinite(v):
            return  # callers treat non-finite as anomalous; never poison moments
        self.count += 1
        if self.count == 1:
            self.mean = v
            self.var = 0.0
            return
        delta = v - self.mean
        self.mean += self.alpha * delta
        self.var = (1.0 - self.alpha) * (self.var + self.alpha * delta * delta)

    @property
    def std(self) -> float:
        return math.sqrt(self.var) if self.var > 0.0 else 0.0

    def zscore(self, value: float) -> float:
        """Deviation of ``value`` from the EW mean in EW-std units.

        0.0 until two samples exist (no spread to judge against). The std is
        floored relative to the mean's magnitude so a perfectly constant
        stream doesn't turn harmless float jitter into an infinite z.
        """
        if self.count < 2:
            return 0.0
        v = float(value)
        if not math.isfinite(v):
            return math.inf
        floor = 1e-8 + 1e-6 * abs(self.mean)
        return (v - self.mean) / max(self.std, floor)


class Metric:
    """Base accumulator. Subclasses implement update/compute/reset."""

    def update(self, value) -> None:
        raise NotImplementedError

    def compute(self) -> float:
        raise NotImplementedError

    def reset(self) -> None:
        raise NotImplementedError


class MeanMetric(Metric):
    def __init__(self, sync_on_compute: bool = False, **_: Any):
        self._sum = 0.0
        self._count = 0

    def update(self, value) -> None:
        self._sum += _to_float(value)
        self._count += 1

    def compute(self) -> float:
        return self._sum / self._count if self._count else math.nan

    def reset(self) -> None:
        self._sum = 0.0
        self._count = 0


class SumMetric(Metric):
    def __init__(self, sync_on_compute: bool = False, **_: Any):
        self._sum = 0.0
        self._updated = False

    def update(self, value) -> None:
        self._sum += _to_float(value)
        self._updated = True

    def compute(self) -> float:
        return self._sum if self._updated else math.nan

    def reset(self) -> None:
        self._sum = 0.0
        self._updated = False


class MaxMetric(Metric):
    def __init__(self, sync_on_compute: bool = False, **_: Any):
        self._max = -math.inf
        self._updated = False

    def update(self, value) -> None:
        self._max = max(self._max, _to_float(value))
        self._updated = True

    def compute(self) -> float:
        return self._max if self._updated else math.nan

    def reset(self) -> None:
        self._max = -math.inf
        self._updated = False


class LastMetric(Metric):
    def __init__(self, **_: Any):
        self._last = math.nan

    def update(self, value) -> None:
        self._last = _to_float(value)

    def compute(self) -> float:
        return self._last

    def reset(self) -> None:
        self._last = math.nan


def _acc_step(state, vec):
    """One donated device-side accumulation step: (sum, max, last) <- vec."""
    s, mx, last = state
    return s + vec, jnp.maximum(mx, vec), vec


_ACC_STEP = jax_compile.guarded_jit(_acc_step, name="metric.acc_step", donate_argnums=(0,))

# materializes a fresh buffer: the initial (sum, max, last) state must be three
# DISTINCT buffers or the next donated step would donate one buffer three times
_ACC_COPY = jax_compile.guarded_jit(lambda v: v + 0, name="metric.acc_copy")

# metric classes whose window result is recoverable from (sum, max, last, count)
# — custom subclasses fall back to the immediate-pull path so their update()
# still sees every raw value
_DRAINABLE = (MeanMetric, SumMetric, MaxMetric, LastMetric)


class MetricAggregator:
    """Dict of metrics with a class-level kill switch.

    Reference: sheeprl/utils/metric.py:17-143. ``compute`` drops NaN results (metrics
    never updated this window), like the reference's NaN-dropping compute.
    """

    disabled: bool = False

    def __init__(self, metrics: Optional[Mapping[str, Any]] = None, raise_on_missing: bool = False):
        self.metrics: Dict[str, Metric] = {}
        self._raise_on_missing = raise_on_missing
        # device-side accumulators: keys-signature -> [(sum, max, last) device vecs, count]
        self._device_acc: Dict[tuple, list] = {}
        for key, value in (metrics or {}).items():
            self.add(key, value)

    def add(self, name: str, metric) -> None:
        if self.disabled:
            return
        if isinstance(metric, Mapping) and "_target_" in metric:
            from sheeprl_tpu.config import instantiate

            metric = instantiate(metric)
        if name in self.metrics:
            raise ValueError(f"Metric {name} already exists")
        self.metrics[name] = metric

    def update(self, name: str, value) -> None:
        if self.disabled:
            return
        if name not in self.metrics:
            if self._raise_on_missing:
                raise KeyError(f"Metric {name} not registered")
            return
        self.metrics[name].update(value)

    def update_from_device(self, metrics: Mapping[str, Any]) -> None:
        """Accumulate a dict of (possibly device-resident) scalars with NO pull.

        A per-key ``float(device_scalar)`` pays a full synchronous host<->device
        round trip EACH (thirteen of them for a 13-metric train dict). Even a
        single stacked
        ``np.asarray`` per call still blocks the host once per iteration, so the
        values stay ON DEVICE in a donated (sum, max, last) accumulator and are
        pulled exactly once per log window, when :meth:`compute` drains it — the
        interaction loop's only blocking sync stays the action fetch.

        Unregistered keys are always filtered, never raised on: callers pass the
        train step's full metric dict, whose keys are a superset of whatever
        subset the user registered (``raise_on_missing`` still guards the
        single-key ``update``). Custom Metric subclasses (whose window result
        may not be recoverable from sum/max/last) keep the immediate stacked
        pull.
        """
        if self.disabled or not metrics:
            return
        keys = [k for k in metrics if k in self.metrics]
        if not keys:
            return
        if not any(isinstance(metrics[k], jax.Array) for k in keys):
            for k in keys:
                self.metrics[k].update(_to_float(metrics[k]))
            return
        deferred = tuple(k for k in keys if type(self.metrics[k]) in _DRAINABLE)
        immediate = [k for k in keys if k not in set(deferred)]
        if immediate:
            host = np.asarray(
                jnp.stack([jnp.asarray(metrics[k], dtype=jnp.float32).mean() for k in immediate])
            )
            for k, v in zip(immediate, host.tolist()):
                self.metrics[k].update(float(v))
        if deferred:
            # eager stack: pure device work, dispatched async, never syncs host
            vec = jnp.stack([jnp.asarray(metrics[k], dtype=jnp.float32).mean() for k in deferred])
            acc = self._device_acc.get(deferred)
            if acc is None:
                self._device_acc[deferred] = [(vec, _ACC_COPY(vec), _ACC_COPY(vec)), 1]
            else:
                acc[0] = _ACC_STEP(acc[0], vec)
                acc[1] += 1

    def precompile_drain(self, keys: Sequence[str], sharding: Any = None) -> None:
        """AOT-compile the device accumulation path for a train metric dict with
        ``keys`` (warmup hook: the loops queue this on the AOT thread so the
        first ``update_from_device`` executes pre-built kernels). Only the
        deferred-drainable subset shapes the kernels, mirroring
        :meth:`update_from_device`'s key filtering. ``sharding`` is where the
        train step leaves its metric scalars (the mesh-replicated placement):
        the stacked vector inherits it, and the executables must be compiled
        for it."""
        if self.disabled:
            return
        deferred = tuple(k for k in keys if k in self.metrics and type(self.metrics[k]) in _DRAINABLE)
        if not deferred:
            return
        vec = jax.ShapeDtypeStruct((len(deferred),), jnp.float32, sharding=sharding)
        _ACC_COPY.aot_compile(vec)
        _ACC_STEP.aot_compile((vec, vec, vec), vec)

    def _drain_device_acc(self) -> None:
        """ONE device->host pull per keys-signature: fold the window's device
        accumulator into the host metrics (log-boundary only)."""
        if not self._device_acc:
            return
        for sig, (state, count) in self._device_acc.items():
            sums, maxes, lasts = (np.asarray(a) for a in jax.device_get(state))
            for i, k in enumerate(sig):
                m = self.metrics.get(k)
                if m is None:  # popped since accumulation
                    continue
                kind = type(m)
                if kind is SumMetric:
                    m.update(float(sums[i]))
                elif kind is MaxMetric:
                    m.update(float(maxes[i]))
                elif kind is LastMetric:
                    m.update(float(lasts[i]))
                else:  # MeanMetric: one update carrying the window mean
                    m.update(float(sums[i]) / count)
        self._device_acc.clear()

    def __contains__(self, name: str) -> bool:
        return name in self.metrics

    def pop(self, name: str) -> None:
        self.metrics.pop(name, None)

    def reset(self) -> None:
        self._device_acc.clear()
        for m in self.metrics.values():
            m.reset()

    def compute(self) -> Dict[str, float]:
        if self.disabled:
            return {}
        self._drain_device_acc()
        out: Dict[str, float] = {}
        for name, m in self.metrics.items():
            value = m.compute()
            if value is None or (isinstance(value, float) and math.isnan(value)):
                continue
            out[name] = value
        return out

    def to(self, device=None) -> "MetricAggregator":  # API-parity no-op (host metrics)
        return self


class RankIndependentMetricAggregator(MetricAggregator):
    """Per-process metrics gathered across hosts at compute time.

    Reference: sheeprl/utils/metric.py:146-195. On single-controller JAX there is one
    host process per pod slice, so gathering is only needed under multi-controller runs.
    """

    def compute(self) -> Dict[str, float]:
        local = super().compute()
        if jax.process_count() > 1:  # pragma: no cover - multihost only
            from jax.experimental import multihost_utils

            keys = sorted(local.keys())
            vals = np.asarray([local[k] for k in keys], dtype=np.float32)
            gathered = multihost_utils.process_allgather(vals)
            return {k: float(np.nanmean(gathered[:, i])) for i, k in enumerate(keys)}
        return local
