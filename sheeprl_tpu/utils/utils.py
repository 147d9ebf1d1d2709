"""Core scalar/pytree helpers shared across the framework.

Functional parity targets (reference: sheeprl/utils/utils.py): ``dotdict`` (:34-60),
``gae`` (:64-100), ``symlog/symexp`` (:148-153), ``two_hot_encoder/decoder`` (:156-205),
``print_config`` (:208-237), ``Ratio`` (:259-300), ``safetanh/safeatanh`` (:304-313).
All device math is JAX (jit-friendly, static shapes); host bookkeeping stays Python.
"""

from __future__ import annotations

import copy
import os
from typing import Any, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.telemetry import trace


class dotdict(dict):
    """Nested dict with attribute access (recursively converts nested mappings).

    Mirrors the reference's config container so algorithm code can write
    ``cfg.algo.mlp_keys.encoder``.
    """

    def __init__(self, *args, **kwargs):
        super().__init__()
        src = dict(*args, **kwargs)
        for k, v in src.items():
            self[k] = v

    @staticmethod
    def _wrap(value):
        if isinstance(value, dotdict):
            return value
        if isinstance(value, Mapping):
            return dotdict(value)
        if isinstance(value, (list, tuple)):
            return type(value)(dotdict._wrap(v) for v in value)
        return value

    def __setitem__(self, key, value):
        super().__setitem__(key, dotdict._wrap(value))

    def __setattr__(self, key, value):
        self[key] = value

    def __getattr__(self, key):
        try:
            return self[key]
        except KeyError as e:
            raise AttributeError(key) from e

    def __delattr__(self, key):
        try:
            del self[key]
        except KeyError as e:
            raise AttributeError(key) from e

    def __deepcopy__(self, memo):
        return dotdict({k: copy.deepcopy(v, memo) for k, v in self.items()})

    def as_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for k, v in self.items():
            if isinstance(v, dotdict):
                out[k] = v.as_dict()
            elif isinstance(v, (list, tuple)):
                out[k] = type(v)(x.as_dict() if isinstance(x, dotdict) else x for x in v)
            else:
                out[k] = v
        return out


def get_nested(cfg: Mapping, dotted: str, default=None):
    node: Any = cfg
    for part in dotted.split("."):
        if isinstance(node, Mapping) and part in node:
            node = node[part]
        else:
            return default
    return node


def set_nested(cfg: Dict, dotted: str, value, create: bool = True):
    parts = dotted.split(".")
    node = cfg
    for part in parts[:-1]:
        if part not in node or not isinstance(node[part], dict):
            if not create:
                raise KeyError(dotted)
            node[part] = dotdict() if isinstance(node, dotdict) else {}
        node = node[part]
    node[parts[-1]] = value


def host_float32(tree):
    """Cast sub-fp32 floating leaves of a pytree to float32 (on device).

    Apply to jitted rollout-step outputs before they leave the device. This is
    a dtype contract, not a transport workaround: rollout products (actions,
    log-probs, values) are stored float32 in the replay/rollout buffers,
    matching the reference, and the AOT train-step specs are derived as
    float32. (A bf16 pull from the chip is a proper ``ml_dtypes`` bfloat16
    array: checked on a TPU v5 lite, PR 22.)
    """
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != jnp.float32
        else x,
        tree,
    )


def resolve_actor_cls(cls_path: Any, default_cls: type, minedojo_cls: type) -> type:
    """Map ``cfg.algo.actor.cls`` (a dotted class path) onto this repo's actor classes.

    The reference resolves the path with ``hydra.utils.get_class`` (e.g.
    dreamer_v3/agent.py:1184); here the selection is by class *basename* so both
    the reference's names (``MinedojoActor``) and this repo's (``MinedojoActorDV2``)
    work. Unrecognized non-default values raise instead of silently building an
    unmasked actor.
    """
    basename = str(cls_path or "").rsplit(".", 1)[-1]
    if basename in ("", "None", default_cls.__name__, "Actor", "ActorDV2"):
        return default_cls
    if "MinedojoActor" in basename:
        return minedojo_cls
    raise ValueError(
        f"Unrecognized actor cls {cls_path!r}: expected a default actor "
        f"({default_cls.__name__!r}) or a MineDojo actor ({minedojo_cls.__name__!r})"
    )


# --------------------------------------------------------------------------------------
# Device math (jit-friendly)
# --------------------------------------------------------------------------------------


def symlog(x: jax.Array) -> jax.Array:
    """Symmetric log squashing (DreamerV3). Reference: sheeprl/utils/utils.py:148-150."""
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x: jax.Array) -> jax.Array:
    """Inverse of :func:`symlog`. Reference: sheeprl/utils/utils.py:152-153."""
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1.0)


def two_hot_encoder(value: jax.Array, support_range: int = 300, num_buckets: int = 255) -> jax.Array:
    """Two-hot encode a scalar tensor over a symlog-spaced support.

    Input shape ``[..., 1]`` -> output ``[..., num_buckets]``.
    Reference semantics: sheeprl/utils/utils.py:156-183 (support is
    ``linspace(-support_range, support_range, num_buckets)`` in symlog space).
    """
    value = symlog(value)
    support = jnp.linspace(-support_range, support_range, num_buckets)
    value = jnp.clip(value, -support_range, support_range)
    idx_above = jnp.sum((support < value).astype(jnp.int32), axis=-1)
    idx_above = jnp.clip(idx_above, 0, num_buckets - 1)
    idx_below = jnp.clip(idx_above - 1, 0, num_buckets - 1)
    below_val = support[idx_below]
    above_val = support[idx_above]
    denom = above_val - below_val
    # When value falls exactly on a support point, idx_below == idx_above and denom == 0.
    safe_denom = jnp.where(denom == 0, 1.0, denom)
    w_above = jnp.where(denom == 0, 1.0, (value[..., 0] - below_val) / safe_denom)
    w_above = jnp.clip(w_above, 0.0, 1.0)
    onehot_below = jax.nn.one_hot(idx_below, num_buckets)
    onehot_above = jax.nn.one_hot(idx_above, num_buckets)
    return onehot_below * (1.0 - w_above)[..., None] + onehot_above * w_above[..., None]


def two_hot_decoder(probs: jax.Array, support_range: int = 300) -> jax.Array:
    """Decode a two-hot/categorical distribution back to a scalar ``[..., 1]``.

    Reference: sheeprl/utils/utils.py:186-205.
    """
    num_buckets = probs.shape[-1]
    support = jnp.linspace(-support_range, support_range, num_buckets)
    value = jnp.sum(probs * support, axis=-1, keepdims=True)
    return symexp(value)


def safetanh(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """tanh with output clamped away from +-1 (stable atanh). Reference: utils.py:304-308."""
    return jnp.clip(jnp.tanh(x), -1.0 + eps, 1.0 - eps)


def safeatanh(y: jax.Array, eps: float = 1e-6) -> jax.Array:
    """atanh with input clamped away from +-1. Reference: utils.py:310-313."""
    return jnp.arctanh(jnp.clip(y, -1.0 + eps, 1.0 - eps))


def gae(
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    next_value: jax.Array,
    num_steps: int,
    gamma: float,
    gae_lambda: float,
):
    """Generalized advantage estimation over a ``[T, B, 1]`` rollout.

    TPU-first: a reverse ``lax.scan`` instead of the reference's Python loop
    (sheeprl/utils/utils.py:64-100). Returns ``(returns, advantages)``.
    """
    del num_steps  # shape is static under jit; kept for API parity

    next_values = jnp.concatenate([values[1:], next_value[None]], axis=0)
    not_done = 1.0 - dones
    deltas = rewards + gamma * next_values * not_done - values

    def body(carry, xs):
        delta, nd = xs
        carry = delta + gamma * gae_lambda * nd * carry
        return carry, carry

    _, adv_rev = jax.lax.scan(body, jnp.zeros_like(next_value), (deltas[::-1], not_done[::-1]))
    advantages = adv_rev[::-1]
    returns = advantages + values
    return returns, advantages


def normalize_tensor(x: jax.Array, eps: float = 1e-8) -> jax.Array:
    return (x - x.mean()) / (x.std() + eps)


def polyak_update(params, target_params, tau: float):
    """EMA/soft target update: ``target = tau * online + (1 - tau) * target``."""
    return jax.tree_util.tree_map(lambda p, tp: tau * p + (1.0 - tau) * tp, params, target_params)


class PlayerParamsSync:
    """One-transfer params pipe: training mesh -> player device.

    Per-leaf cross-backend transfers each pay a synchronous host<->device round
    trip, so the per-iteration player refresh ravels the whole param tree
    into ONE flat vector on the mesh (call :meth:`ravel` inside the jitted train
    step), ships that single array, and unravels it on the player device. The
    reference ships trainer->player params the same way, as one flattened vector
    (torch ``parameters_to_vector``, sheeprl/algos/ppo/ppo_decoupled.py:302,550).
    """

    def __init__(self, player_params):
        from jax.flatten_util import ravel_pytree

        self._ravel_pytree = ravel_pytree
        _, self._unravel = ravel_pytree(player_params)
        self._unravel_jit = jax_compile.guarded_jit(self._unravel, name="sync.unravel")

    def ravel(self, params) -> jax.Array:
        """Flatten on the training mesh — call from inside the jitted train step."""
        return self._ravel_pytree(params)[0]

    def pull(self, flat: jax.Array, device):
        """One cross-backend transfer + on-device unflatten -> player param tree."""
        with trace.span("player.pull", bytes=flat.nbytes):
            return self._unravel_jit(jax.device_put(flat, device))


class DreamerPlayerSync:
    """Mesh -> player-device param pipe for the dreamer-family rollout policies.

    A dreamer player only needs the obs->latent->action subset of the world model
    (encoder + the recurrent/representation step models, plus the transition model
    and learned initial state for the DV3 line) and the behavior actor — not the
    decoder, reward, or continue heads. This helper ravels exactly that subset
    into ONE flat vector inside the jitted train step (:meth:`ravel`) and
    refreshes the player every ``algo.player_sync_every`` train calls with a
    single cross-backend transfer (:meth:`push`), the same amortization the SAC
    family uses and the same one-flat-vector shape the reference's decoupled
    param broadcast ships (sheeprl/algos/ppo/ppo_decoupled.py:302,550).

    With ``fabric.player_on_host=False`` the player shares the mesh device and
    :meth:`push` just rebinds the mesh references (zero transfers).
    """

    def __init__(self, runtime, params, wm_keys: Sequence[str], actor_name: str = "actor", every: int = 1):
        self._runtime = runtime
        self._wm_keys = tuple(wm_keys)
        self._actor_name = actor_name
        self._every = max(1, int(every))
        self._calls = 0
        self.enabled = bool(runtime.player_on_host)
        if self.enabled:
            self._sync = PlayerParamsSync(self.subset(params))
            self._ravel_jit = jax_compile.guarded_jit(self._sync.ravel, name="sync.ravel")

    def subset(self, params):
        wm = params["world_model"]
        return ({k: wm[k] for k in self._wm_keys}, params[self._actor_name])

    def ravel(self, params) -> Optional[jax.Array]:
        """Call inside the jitted train step; one flat vector on the mesh (or None
        when the player lives on the mesh and no transfer is needed).

        With a >1 cadence most train calls would discard the vector, so the
        in-graph ravel is skipped and the cadence-hit :meth:`push` ravels the
        then-current params with its own dispatch instead."""
        return self._sync.ravel(self.subset(params)) if self.enabled and self._every == 1 else None

    def push(self, player, params, flat: Optional[jax.Array] = None, force: bool = False) -> None:
        """Host side, after a train call: refresh the player's param copies.

        ``flat`` is the train step's raveled output (avoids an extra dispatch);
        ``force`` bypasses the cadence (initial placement, final pre-test flush).
        """
        with trace.span("player.push") as sp:
            if not self.enabled:
                sp.set(skipped="rebind")
                player.wm_params = params["world_model"]
                player.actor_params = params[self._actor_name]
                return
            if force:
                self._calls = 0  # the player is fresh: restart the staleness window
            else:
                self._calls += 1
                if self._calls % self._every != 0:
                    sp.set(skipped="cadence")
                    return
            if flat is None:
                with trace.span("player.ravel"):
                    flat = self._ravel_jit(self.subset(params))
            wm, actor = self._sync.pull(flat, self._runtime.player_device)
            player.wm_params = wm
            player.actor_params = actor


# --------------------------------------------------------------------------------------
# Host-side bookkeeping
# --------------------------------------------------------------------------------------


class Ratio:
    """Replay-ratio scheduler: how many gradient steps to run per batch of policy steps.

    Host-side (drives the number of jitted update calls; must stay outside jit).
    Reference: sheeprl/utils/utils.py:259-300.
    """

    def __init__(self, ratio: float, pretrain_steps: int = 0):
        if pretrain_steps < 0:
            raise ValueError(f"'pretrain_steps' must be non-negative, got {pretrain_steps}")
        if ratio < 0:
            raise ValueError(f"'ratio' must be non-negative, got {ratio}")
        self._pretrain_steps = pretrain_steps
        self._ratio = ratio
        self._prev: Optional[float] = None

    def __call__(self, step: int) -> int:
        if self._ratio == 0:
            return 0
        if self._prev is None:
            self._prev = step
            repeats = int(step * self._ratio)
            if self._pretrain_steps > 0:
                if step < self._pretrain_steps:
                    import warnings

                    warnings.warn(
                        "The number of pretrain steps is greater than the number of current steps. This could lead "
                        f"to a higher ratio than the one specified ({self._ratio}). Setting the 'pretrain_steps' "
                        "equal to the number of current steps."
                    )
                    self._pretrain_steps = step
                repeats = int(self._pretrain_steps * self._ratio)
            return repeats
        repeats = int((step - self._prev) * self._ratio)
        self._prev += repeats / self._ratio
        return repeats

    def state_dict(self) -> Dict[str, Any]:
        return {"_ratio": self._ratio, "_prev": self._prev, "_pretrain_steps": self._pretrain_steps}

    def load_state_dict(self, state: Mapping[str, Any]) -> "Ratio":
        self._ratio = state["_ratio"]
        self._prev = state["_prev"]
        self._pretrain_steps = state["_pretrain_steps"]
        return self


def polynomial_decay(
    current_step: int,
    *,
    initial: float = 1.0,
    final: float = 0.0,
    max_decay_steps: int = 100,
    power: float = 1.0,
) -> float:
    """Host-side polynomial decay for coefficients (reference utils.py:120-131)."""
    if current_step > max_decay_steps or initial == final:
        return final
    return (initial - final) * ((1 - current_step / max_decay_steps) ** power) + final


def print_config(cfg: Mapping, indent: int = 0) -> None:
    """Pretty-print the resolved config tree (reference: utils.py:208-237, rich tree)."""
    for key in sorted(cfg.keys()):
        value = cfg[key]
        if isinstance(value, Mapping):
            print(" " * indent + f"{key}:")
            print_config(value, indent + 2)
        else:
            print(" " * indent + f"{key}: {value!r}")


def save_configs(cfg, log_dir: str) -> None:
    """Persist the resolved config next to the run artifacts (sidecar convention)."""
    import yaml

    os.makedirs(log_dir, exist_ok=True)
    plain = cfg.as_dict() if isinstance(cfg, dotdict) else dict(cfg)
    with open(os.path.join(log_dir, "config.yaml"), "w") as f:
        yaml.safe_dump(plain, f, sort_keys=False)


def unwrap_fabric(module):  # pragma: no cover - API-parity shim
    """No DDP wrappers exist in the TPU build; identity (reference: utils.py:240-249)."""
    return module


NUMPY_TO_JAX_DTYPE = {
    np.dtype("float64"): jnp.float32,
    np.dtype("float32"): jnp.float32,
    np.dtype("float16"): jnp.float16,
    np.dtype("int64"): jnp.int32,
    np.dtype("int32"): jnp.int32,
    np.dtype("int16"): jnp.int16,
    np.dtype("int8"): jnp.int8,
    np.dtype("uint8"): jnp.uint8,
    np.dtype("bool"): jnp.bool_,
}
