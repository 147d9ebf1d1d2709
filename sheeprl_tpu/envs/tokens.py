"""A host environment of token sequences, for language-model policies.

An episode is one sequence of ``prompt_tokens + sampled_tokens`` steps. Reset
draws a prompt (Zipf-distributed ids over the vocabulary, from the seed) and
shows the begin-of-sequence token. While the prompt lasts the env ignores the
action and feeds its own next token; the observation's ``sampled`` flag is 0 on
those steps, so the learner leaves them out of its loss. After the prompt every
action is the next token and comes back as the next observation (flag 1). The
last step pays one terminal reward from a seeded checker, a function of the
sampled tokens alone (so it can be verified from a transcript): 1 if more than
half of them lie in the checker's target half of the vocabulary, else 0.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import gymnasium as gym
import numpy as np


def zipf_probabilities(vocab_size: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab_size + 1, dtype=np.float64) ** a
    return p / p.sum()


class TokenEnv(gym.Env):
    def __init__(
        self,
        id: str = "tokens",
        vocab_size: int = 16384,
        prompt_tokens: int = 1024,
        sampled_tokens: int = 7168,
        bos_token: int = 1,
        zipf_a: float = 1.1,
        checker_seed: int = 0,
        seed: Optional[int] = None,
    ):
        if not 0 <= bos_token < vocab_size:
            raise ValueError(f"bos_token {bos_token} is outside the vocabulary of {vocab_size}")
        self.vocab_size = int(vocab_size)
        self.prompt_tokens = int(prompt_tokens)
        self.sampled_tokens = int(sampled_tokens)
        self.episode_length = self.prompt_tokens + self.sampled_tokens
        self.bos_token = int(bos_token)
        self.observation_space = gym.spaces.Dict(
            {
                "tokens": gym.spaces.Box(0, self.vocab_size - 1, (1,), np.int32),
                "sampled": gym.spaces.Box(0, 1, (1,), np.int32),
            }
        )
        self.action_space = gym.spaces.Discrete(self.vocab_size)
        self.render_mode = None
        self._p = zipf_probabilities(self.vocab_size, float(zipf_a))
        # the checker is the task: one for every env of a run, whatever their own seeds
        self.target = np.zeros(self.vocab_size, dtype=bool)
        self.target[np.random.default_rng(int(checker_seed)).permutation(self.vocab_size)[: self.vocab_size // 2]] = True
        self._rng = np.random.default_rng(seed)
        self._prompt = np.zeros(self.prompt_tokens, np.int32)
        self._sampled = np.zeros(self.sampled_tokens, np.int32)
        self._t = 0

    def check(self, sampled: np.ndarray) -> float:
        """The terminal reward of a transcript's sampled tokens."""
        return float(np.mean(self.target[np.asarray(sampled, np.int64)]) > 0.5)

    def _obs(self, token: int) -> Dict[str, np.ndarray]:
        flag = 1 if self._t >= self.prompt_tokens else 0
        return {"tokens": np.array([token], np.int32), "sampled": np.array([flag], np.int32)}

    def reset(self, seed: Optional[int] = None, options: Optional[Dict[str, Any]] = None):
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        self._prompt = self._rng.choice(self.vocab_size, size=self.prompt_tokens, p=self._p).astype(np.int32)
        self._t = 0
        return self._obs(self.bos_token), {}

    def step(self, action):
        if self._t < self.prompt_tokens:
            token = int(self._prompt[self._t])
        else:
            token = int(np.asarray(action).reshape(-1)[0])
            if not 0 <= token < self.vocab_size:
                raise ValueError(f"token {token} is outside the vocabulary of {self.vocab_size}")
            self._sampled[self._t - self.prompt_tokens] = token
        self._t += 1
        done = self._t >= self.episode_length
        reward = self.check(self._sampled) if done else 0.0
        return self._obs(token), reward, done, False, {}

    def render(self):
        return None

    def close(self):
        pass
