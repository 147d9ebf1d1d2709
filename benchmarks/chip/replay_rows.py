"""The replay rows every run is fed: made from the seed by the benchmark, never
by the program. Row ``r`` carries ``r`` in its first three pixel bytes, so a
sampled batch names the rows it came from and `check.py` can compare what
arrived on the device with what the seed put into the buffer."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

# Crafter's reward alphabet: nothing, the health penalty/bonus, an achievement.
_REWARDS = np.array([0.0, 0.1, -0.1, 1.0], dtype=np.float32)
_REWARD_P = np.array([0.9, 0.04, 0.04, 0.02])


def make_rows(seed: int, n_rows: int, config: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """``[n_rows, 1, ...]`` arrays in the layout ``rb.add`` takes (one env)."""
    rng = np.random.default_rng(seed)
    rows: Dict[str, np.ndarray] = {}
    for key, spec in config["obs"].items():
        if spec["dtype"] == "uint8":
            n_bytes = n_rows * int(np.prod(spec["shape"]))
            words = rng.integers(0, 2**64, size=-(-n_bytes // 8), dtype=np.uint64)  # 8 bytes a draw
            px = words.view(np.uint8)[:n_bytes].reshape(n_rows, 1, *spec["shape"])
            ids = np.arange(n_rows, dtype=np.uint32)
            flat = px.reshape(n_rows, -1)
            for b in range(3):
                flat[:, b] = (ids >> (8 * b)) & 0xFF
            rows[key] = px
    rewards = rng.choice(_REWARDS, size=(n_rows, 1, 1), p=_REWARD_P)
    # episode boundaries: geometric lengths around the mix's mean, from the seed
    ends = rng.random(n_rows) < 1.0 / float(traffic["episode_len_mean"])
    died = ends & (rng.random(n_rows) < 0.5)
    terminated = died.astype(np.float32).reshape(n_rows, 1, 1)
    is_first = np.roll(ends, 1).astype(np.float32).reshape(n_rows, 1, 1)
    is_first[0] = 1.0
    n_act = int(config["actions"]["n"])
    actions = np.eye(n_act, dtype=np.float32)[rng.integers(0, n_act, size=n_rows)].reshape(n_rows, 1, n_act)
    for key, spec in config["obs"].items():
        if spec["dtype"] != "uint8":  # Crafter's reward-as-observation vector
            rows[key] = rewards.reshape(n_rows, 1, *spec["shape"]).astype(spec["dtype"])
    rows.update(
        rewards=rewards,
        terminated=terminated,
        truncated=(ends & ~died).astype(np.float32).reshape(n_rows, 1, 1),
        is_first=is_first,
        actions=actions,
    )
    return rows


def row_ids(pixels: np.ndarray) -> np.ndarray:
    """Row numbers back out of sampled pixels ``[..., C, H, W]``."""
    flat = pixels.reshape(*pixels.shape[:-3], -1)[..., :3].astype(np.uint32)
    return flat[..., 0] | (flat[..., 1] << 8) | (flat[..., 2] << 16)
