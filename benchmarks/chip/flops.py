"""The yardstick's operation counts: model FLOPs of one gradient step, from shapes.

``dv3_step_flops`` is a copy of ``benchmarks/analytic_flops.py`` (the original
stays for ``bench.py``); it reads the sizes from the configuration file's
``sizes`` instead of the program's config tree, so no later PR of the program
can move it. A new model adds its count to ``COUNTS`` under the name its
configuration file gives as ``flops``.

Counting rules: a matmul [m,k]@[k,n] is 2*m*k*n; a convolution is
2 * out_spatial * C_out * C_in * k*k per sample; a path that receives parameter
gradients costs 3x its forward, a path that does not costs 1x; recomputed
operations, LayerNorms, activations and softmaxes are not counted.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

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


COUNTS = {"dv3_step_flops": dv3_step_flops}


def step_flops(config: Dict[str, Any]) -> float:
    """Model FLOPs of one gradient step of ``config`` (its ``flops`` names the count)."""
    name = config["flops"]
    if name not in COUNTS:
        raise KeyError(f"configuration {config['name']!r} names the FLOP count {name!r}; flops.py has {sorted(COUNTS)}")
    return float(COUNTS[name](config["sizes"])["total"])
