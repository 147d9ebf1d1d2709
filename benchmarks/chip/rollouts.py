"""The rollouts every run of a token-policy cell is fed: made from the seed by the
benchmark, never by the program. A rollout is what ``ppo_recurrent.main()`` holds
when its env loop ends: ``[T, n_envs, 1]`` float32 arrays of the observations
(``tokens``, ``sampled``), the actions, rewards and dones. The old log-probs and
values are not here: the driver has the program score the pool in set-up.

Token ids are Zipf(``zipf_a``) over the held ids, so that the first expert
layers route unevenly; a step's action is the next step's token (the last
action is drawn too); one terminal reward a sequence from ``rewards``.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def zipf_probabilities(vocab: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** a
    return p / p.sum()


def make_pool(seed: int, sizes: Dict[str, Any], traffic: Dict[str, Any]) -> List[Dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    t, n_envs, vocab, prompt = int(sizes["sequence"]), int(sizes["batch"]), int(sizes["vocab"]), int(sizes["prompt"])
    p = zipf_probabilities(vocab, float(traffic["zipf_a"]))
    pool = []
    for _ in range(int(traffic["pool_rollouts"])):
        ids = rng.choice(vocab, size=(t + 1, n_envs), p=p).astype(np.float32)
        rewards = np.zeros((t, n_envs, 1), np.float32)
        rewards[-1, :, 0] = rng.choice(np.asarray(traffic["rewards"], np.float32), size=n_envs)
        dones = np.zeros((t, n_envs, 1), np.float32)
        dones[-1] = 1.0
        sampled = np.zeros((t, n_envs, 1), np.float32)
        sampled[prompt:] = 1.0
        actions = ids[1:, :, None]
        prev_actions = np.concatenate([np.zeros((1, n_envs, 1), np.float32), actions[:-1]], axis=0)
        pool.append({
            "tokens": ids[:-1, :, None], "sampled": sampled, "actions": actions, "prev_actions": prev_actions,
            "rewards": rewards, "dones": dones,
        })
    return pool
