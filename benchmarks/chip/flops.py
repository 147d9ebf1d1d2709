"""The yardstick's operation counts: model FLOPs of one gradient step, from shapes.

A configuration file names its count as ``flops``: ``<file>:<function>`` is that
function of ``<file>.py`` beside this file; a bare name (the two accepted
configuration files have one each) is a function of this file or, where this file
has none of that name, of ``flops_<the name's first word>.py``. Nothing is
registered and no count file needs loading before another: `count_of` finds the
file when it is asked. A count file offers

- the count itself, ``f(sizes)`` or ``f(sizes, pairs_here)`` (the (token, slot)
  pairs an expert layer computed on this chip, the program's ``Moe/pairs_here``):
  FLOPs of ONE gradient step by the scope the program runs the part under, with
  their ``total``;
- ``UNCOUNTED``: the program's scopes that hold no counted work (`scopes.py` looks
  for the counted parts and for these);
- ``LAYERS``, where several configurations share a per-layer metric: for a layer
  of ``BENCHMARK.json`` the scopes whose device time is that layer's;
- ``kernels(sizes, pairs_here)``, where the program runs Pallas kernels: for each
  family (``gmm``, ``attention``) the scope its kernels run under and the least
  ``flops`` and ``bytes`` a step.

This file is also the count file of the DreamerV3 configurations:
``dv3_step_flops`` is a copy of ``benchmarks/analytic_flops.py`` (the original
stays for ``bench.py``); it reads the sizes from the configuration file's
``sizes`` instead of the program's config tree, so no later PR of the program
can move it.

Counting rules: a matmul [m,k]@[k,n] is 2*m*k*n; a convolution is
2 * out_spatial * C_out * C_in * k*k per sample; a path that receives parameter
gradients costs 3x its forward, a path that does not costs 1x; recomputed
operations, LayerNorms, activations and softmaxes are not counted.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from common import load_module  # noqa: E402

# dv3.train's scopes that hold no counted work (the optimizers, the return moments, the target's average, the player's copy)
UNCOUNTED = ("world_opt", "actor_opt", "critic_opt", "moments", "target_ema", "player_ravel")


def _mm(m: float, k: float, n: float) -> float:
    return 2.0 * m * k * n


def _mlp(n_samples: float, in_dim: int, hidden: Sequence[int], out_dim: int) -> float:
    dims = [in_dim, *hidden, out_dim]
    return sum(_mm(n_samples, a, b) for a, b in zip(dims[:-1], dims[1:]))


def _encoder_convs(n_samples: float, in_ch: int, mult: int, image: int = 64, stages: int = 4, k: int = 4) -> float:
    """Stride-2 conv stack: image -> image/2**stages (agent.py CNNEncoder)."""
    flops = 0.0
    c_in, side = in_ch, image
    for i in range(stages):
        c_out = (2**i) * mult
        side //= 2
        flops += _mm(n_samples * side * side, c_in * k * k, c_out)  # = 2*out*cin*k*k*cout
        c_in = c_out
    return flops


def _decoder_convs(n_samples: float, out_ch: int, mult: int, image: int = 64, stages: int = 4, k: int = 4) -> float:
    """Mirror transposed-conv stack 4x4 -> image (agent.py CNNDecoder).

    A stride-2 transposed conv [C_in, s, s] -> [C_out, 2s, 2s] costs the same
    matmul volume as the forward conv of the mirrored shape: 2 * (2s)^2/4*k*k...
    counted here as 2 * out_spatial * C_out * C_in * k*k / stride^2 aggregated
    via the input spatial extent (each input pixel drives k*k*C_in*C_out MACs).
    """
    flops = 0.0
    side = image // (2**stages)
    c_in = (2 ** (stages - 1)) * mult
    channels = [(2**i) * mult for i in reversed(range(stages - 1))] + [out_ch]
    for c_out in channels:
        flops += _mm(n_samples * side * side, c_in * k * k, c_out)
        side *= 2
        c_in = c_out
    return flops


def dv3_step_flops(sizes: Dict[str, Any]) -> Dict[str, float]:
    """Analytic FLOPs for ONE DreamerV3 gradient step at the configuration's sizes.

    Returns a per-part breakdown plus the ``total``.
    """
    mult = int(sizes["cnn_channels_multiplier"])
    deter = int(sizes["recurrent_state_size"])
    stoch = int(sizes["stochastic_size"]) * int(sizes["discrete_size"])
    dense = int(sizes["dense_units"])
    layers = int(sizes["mlp_layers"])
    horizon = int(sizes["horizon"])
    image = int(sizes["image"])
    batch, seq = int(sizes["batch"]), int(sizes["sequence"])
    stages = 4
    embed = (2 ** (stages - 1)) * mult * (image // 2**stages) ** 2
    latent = deter + stoch
    n_act = int(sizes["actions"])
    bins = int(sizes["bins"])

    N = float(batch * seq)  # dynamic-phase samples
    M = float(batch * seq)  # imagination lanes
    H = float(horizon)

    def recurrent(n):
        # input MLP (stoch+act -> dense) + fused LayerNorm-GRU ([feat,h] -> 3*deter)
        return _mm(n, stoch + n_act, dense) + _mm(n, dense + deter, 3 * deter)

    def transition(n):
        return _mlp(n, deter, [int(sizes["transition_hidden"])], stoch)

    def representation(n):
        return _mlp(n, deter + embed, [int(sizes["representation_hidden"])], stoch)

    def head(n, out_dim):
        return _mlp(n, latent, [dense] * layers, out_dim)

    parts: Dict[str, float] = {}
    # ---- dynamic learning: everything here gets world-model gradients (x3)
    parts["encoder"] = 3 * _encoder_convs(N, 3, mult, image, stages)
    parts["dynamic_scan"] = 3 * (recurrent(N) + transition(N) + representation(N))
    parts["decoder"] = 3 * (_mm(N, latent, embed) + _decoder_convs(N, 3, mult, image, stages))
    parts["reward_head"] = 3 * head(N, bins)
    parts["continue_head"] = 3 * head(N, 1)
    # ---- imagination: REINFORCE actor -> world-model rollout is forward-only,
    # the actor forward is trained (x3)
    parts["imagination_rollout"] = H * (recurrent(M) + transition(M))
    parts["imagination_actor"] = 3 * H * _mlp(M, latent, [dense] * layers, n_act)
    # reward, online-critic value, and continue predictions over the imagined
    # trajectories for the lambda targets (no grad)
    parts["imagination_heads"] = head(H * M, bins) + head(H * M, bins) + head(H * M, 1)
    # ---- critic update: trained forward+backward over [H, M], target critic fwd
    parts["critic_update"] = 3 * head(H * M, bins)
    parts["target_critic"] = head(H * M, bins)
    parts["total"] = sum(parts.values())
    return parts


def count_of(config: Dict[str, Any]) -> Tuple[Any, Callable[..., Dict[str, float]]]:
    """(count file, count) that ``config["flops"]`` names, among the files beside this one."""
    file, _, function = str(config["flops"]).rpartition(":")
    if not file:
        file = "flops" if function in globals() else "flops_" + function.split("_")[0]
    module = load_module("", file, HERE)
    count = getattr(module, function, None)
    if not callable(count):
        raise KeyError(f"configuration {config.get('name')!r} names the FLOP count {config['flops']!r}: {file}.py has no such function")
    return module, count


def step_parts(config: Dict[str, Any], pairs_here: Optional[float] = None) -> Dict[str, float]:
    """Model FLOPs of one gradient step of ``config`` by scope, with their ``total``; ``pairs_here``
    (where the run has the program's count of them) goes to a count that takes it."""
    count = count_of(config)[1]
    return count(config["sizes"]) if pairs_here is None else count(config["sizes"], pairs_here)


def step_flops(config: Dict[str, Any], pairs_here: Optional[float] = None) -> float:
    """Model FLOPs of one gradient step of ``config`` (its ``flops`` names the count)."""
    return float(step_parts(config, pairs_here)["total"])


def scopes_of(config: Dict[str, Any]) -> Tuple[str, ...]:
    """The scopes to look for in a capture of ``config``'s train program: the parts its count file
    counts, then the ones it lists as uncounted."""
    module, count = count_of(config)
    return tuple(k for k in count(config["sizes"]) if k != "total") + tuple(getattr(module, "UNCOUNTED", ()))


def layer_scopes(config: Dict[str, Any], layer: str) -> Tuple[str, ...]:
    """The scopes whose device time is ``layer``'s in ``config`` (its count file's ``LAYERS``); () where it has none."""
    return tuple(getattr(count_of(config)[0], "LAYERS", {}).get(layer, ()))


def kernel_least(config: Dict[str, Any], family: str, pairs_here: Optional[float] = None) -> Optional[Dict[str, Any]]:
    """``scope``, ``flops`` and ``bytes``: the least a step that the Pallas kernels of ``family`` must do
    in ``config``, and the scope they run under; None where its count file names no such kernels."""
    kernels = getattr(count_of(config)[0], "kernels", None)
    return kernels(config["sizes"], pairs_here).get(family) if kernels else None
