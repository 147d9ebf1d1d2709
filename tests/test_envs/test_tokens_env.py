"""The token environment (``sheeprl_tpu/envs/tokens.py``): forced prompt steps, sampled
steps, the loss flag, one verifiable terminal reward, and its place in ``make_env``."""

import numpy as np
import pytest

from sheeprl_tpu.envs.tokens import TokenEnv, zipf_probabilities


def _episode(env, actions):
    obs, _ = env.reset(seed=5)
    rows = [obs]
    rewards, dones = [], []
    for a in actions:
        obs, reward, terminated, truncated, _ = env.step(a)
        rows.append(obs)
        rewards.append(reward)
        dones.append(terminated or truncated)
    return rows, rewards, dones


def test_prompt_is_fed_then_actions_come_back_and_the_flag_separates_them():
    env = TokenEnv(vocab_size=50, prompt_tokens=4, sampled_tokens=6, bos_token=1, seed=0)
    actions = [7, 8, 9, 10, 11, 12, 13, 14, 15, 16]
    rows, rewards, dones = _episode(env, actions)
    tokens = [int(r["tokens"][0]) for r in rows]
    flags = [int(r["sampled"][0]) for r in rows]
    assert tokens[0] == 1 and flags[:4] == [0, 0, 0, 0] and flags[4:10] == [1] * 6  # 4 forced steps, 6 that count
    assert tokens[1:5] == [int(t) for t in env._prompt]  # the env's own tokens, whatever the policy sampled
    assert tokens[5:11] == actions[4:]  # then every action is the next token
    assert dones == [False] * 9 + [True] and rewards[:9] == [0.0] * 9 and rewards[9] in (0.0, 1.0)
    assert env.episode_length == 10


def test_reward_is_a_function_of_the_sampled_tokens_alone():
    env = TokenEnv(vocab_size=40, prompt_tokens=3, sampled_tokens=5, checker_seed=9, seed=1)
    inside, outside = np.nonzero(env.target)[0], np.nonzero(~env.target)[0]
    assert len(inside) == 20
    for sampled, want in (([int(i) for i in inside[:5]], 1.0), ([int(o) for o in outside[:5]], 0.0)):
        _, rewards, _ = _episode(env, [0, 0, 0] + sampled)
        assert rewards[-1] == want == env.check(np.array(sampled))
    # the same checker for every env of a run, whatever their own seeds
    assert np.array_equal(env.target, TokenEnv(vocab_size=40, prompt_tokens=3, sampled_tokens=5, checker_seed=9, seed=77).target)


def test_same_seed_same_prompt_and_ids_are_zipf():
    a = TokenEnv(vocab_size=1000, prompt_tokens=4000, sampled_tokens=1, seed=3)
    b = TokenEnv(vocab_size=1000, prompt_tokens=4000, sampled_tokens=1, seed=4)
    a.reset(seed=11), b.reset(seed=11)
    assert np.array_equal(a._prompt, b._prompt)
    p = zipf_probabilities(1000, 1.1)
    assert p[0] > 0.1 and abs(float(np.mean(a._prompt == 0)) - p[0]) < 0.03  # id 0 is the most frequent by far


def test_out_of_vocabulary_action_is_an_error():
    env = TokenEnv(vocab_size=10, prompt_tokens=1, sampled_tokens=2, seed=0)
    env.reset(seed=0)
    env.step(3)
    with pytest.raises(ValueError, match="outside the vocabulary"):
        env.step(10)


def test_make_env_builds_it_from_the_config_group():
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.utils.env import make_env

    cfg = compose(config_name="config", overrides=[
        "exp=ppo_recurrent_lfm2_tokens", "algo.lm.vocab_held=32", "env.wrapper.prompt_tokens=2", "env.wrapper.sampled_tokens=3",
    ])
    env = make_env(cfg, seed=5, rank=0)()
    assert env.action_space.n == 32 and set(env.observation_space.spaces) == {"tokens", "sampled"}
    obs, _ = env.reset(seed=5)
    assert obs["tokens"].dtype == np.int32 and int(obs["sampled"][0]) == 0
    for _ in range(5):
        obs, reward, terminated, truncated, info = env.step(1)
    assert terminated and "episode" in info  # RecordEpisodeStatistics sees the episode end
