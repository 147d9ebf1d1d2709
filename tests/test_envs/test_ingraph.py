"""Trajectory parity and zero-transfer guarantees for the in-graph env backend.

Parity contract (howto/ingraph_envs.md): with ``dtype=float64`` the eager
per-op dynamics are BIT-equal to the Gymnasium reference (same expression
order, same operand dtypes); under ``jit``/``scan`` XLA's FMA contraction can
drift the f64 state by 1-2 ULP per step, which the f32 observation cast
absorbs — so the scanned tests assert exact f32 obs/reward/done parity while
the eager tests assert raw f64 state bit-parity. Episode boundaries are
covered by injecting our reset state into the Gymnasium env and continuing.
"""

from __future__ import annotations

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

from sheeprl_tpu.envs.ingraph import CartPole, GridWorld, Pendulum, autoreset_step

pytestmark = pytest.mark.ingraph


def _sync_gym_to(gym_env, y) -> None:
    """Reset the Gymnasium env's bookkeeping and inject our state into it."""
    gym_env.reset()
    gym_env.unwrapped.state = np.asarray(y, dtype=np.float64)


def test_cartpole_eager_f64_bit_parity_with_resets():
    """>=200 steps of eager f64 CartPole match Gymnasium BIT-for-bit, with the
    episode boundaries crossed by re-seeding both sides from our reset."""
    with enable_x64():
        env = CartPole()
        params = env.default_params(dtype=jnp.float64)
        gym_env = gym.make("CartPole-v1", disable_env_checker=True)
        key = jax.random.PRNGKey(0)
        key, k0 = jax.random.split(key)
        state, _ = env.reset(k0, params)
        _sync_gym_to(gym_env, state.y)

        rng = np.random.default_rng(7)
        episodes = 0
        for _ in range(250):
            a = int(rng.integers(0, 2))
            key, ks = jax.random.split(key)
            state, obs, reward, done, info = env.step(ks, state, jnp.int32(a), params)
            g_obs, g_reward, g_term, _g_trunc, _ = gym_env.step(a)
            np.testing.assert_array_equal(
                np.asarray(state.y), np.asarray(gym_env.unwrapped.state, dtype=np.float64)
            )
            np.testing.assert_array_equal(np.asarray(obs), g_obs)
            assert float(reward) == float(g_reward) == 1.0
            assert bool(info["terminated"]) == bool(g_term)
            if bool(done):
                episodes += 1
                key, kr = jax.random.split(key)
                state, _ = env.reset(kr, params)
                _sync_gym_to(gym_env, state.y)
        assert episodes >= 2, "random policy should end several episodes in 250 steps"
        gym_env.close()


def test_cartpole_scanned_autoreset_parity():
    """The fused scan path (autoreset_step under jit+lax.scan) reproduces the
    Gymnasium transition at every step — f32 obs/reward/done — including the
    auto-reset boundaries, where the pre-reset obs rides in terminal_obs and
    the emitted obs is already the next episode's start."""
    T = 300
    with enable_x64():
        env = CartPole()
        params = env.default_params(dtype=jnp.float64)
        step = autoreset_step(env, params)
        key = jax.random.PRNGKey(3)
        key, k0 = jax.random.split(key)
        init_state, _ = env.reset(k0, params)
        rng = np.random.default_rng(11)
        actions = jnp.asarray(rng.integers(0, 2, size=(T,)), dtype=jnp.int32)
        keys = jax.random.split(key, T)

        def body(state, xs):
            k, a = xs
            state, obs, reward, done, info = step(k, state, a)
            return state, (obs, reward, done, info["terminal_obs"], state.y)

        _, (obs_seq, rew_seq, done_seq, term_obs_seq, y_seq) = jax.jit(
            lambda s: jax.lax.scan(body, s, (keys, actions))
        )(init_state)
        obs_seq, rew_seq, done_seq, term_obs_seq, y_seq = jax.tree_util.tree_map(
            np.asarray, (obs_seq, rew_seq, done_seq, term_obs_seq, y_seq)
        )

        gym_env = gym.make("CartPole-v1", disable_env_checker=True)
        _sync_gym_to(gym_env, init_state.y)
        boundaries = 0
        for t in range(T):
            g_obs, g_reward, g_term, g_trunc, _ = gym_env.step(int(actions[t]))
            # the pre-reset obs always tracks the reference transition
            np.testing.assert_array_equal(term_obs_seq[t], g_obs)
            assert float(rew_seq[t]) == float(g_reward)
            assert bool(done_seq[t]) == bool(g_term or g_trunc)
            if bool(done_seq[t]):
                boundaries += 1
                # auto-reset: the emitted obs is a fresh episode, not the terminal one
                assert not np.array_equal(obs_seq[t], term_obs_seq[t])
            # resync gym (and its TimeLimit) to the scan's post-step state so each
            # step is an independent one-step reference, reset branches included
            _sync_gym_to(gym_env, y_seq[t])
        assert boundaries >= 2
        gym_env.close()


def test_pendulum_eager_f64_parity_and_truncation():
    """200 steps of eager f64 Pendulum match Gymnasium bit-for-bit (state),
    exactly (f32 obs/reward), and both sides truncate at step 200."""
    with enable_x64():
        env = Pendulum()
        params = env.default_params(dtype=jnp.float64)
        gym_env = gym.make("Pendulum-v1", disable_env_checker=True)
        key = jax.random.PRNGKey(1)
        key, k0 = jax.random.split(key)
        state, _ = env.reset(k0, params)
        _sync_gym_to(gym_env, state.y)

        rng = np.random.default_rng(5)
        for t in range(200):
            a = rng.uniform(-2.0, 2.0, size=(1,))
            key, ks = jax.random.split(key)
            state, obs, reward, done, info = env.step(ks, state, jnp.asarray(a), params)
            g_obs, g_reward, g_term, g_trunc, _ = gym_env.step(a)
            np.testing.assert_array_equal(
                np.asarray(state.y), np.asarray(gym_env.unwrapped.state, dtype=np.float64)
            )
            np.testing.assert_array_equal(np.asarray(obs), g_obs)
            assert np.float32(g_reward) == np.asarray(reward)
            assert not bool(info["terminated"]) and not bool(g_term)
            if t < 199:
                assert not bool(done) and not bool(g_trunc)
        assert bool(done) and bool(info["truncated"]) and bool(g_trunc)
        gym_env.close()


def test_gridworld_procedural_layouts():
    """Same key => same scenario; distinct keys => distinct scenarios; every
    layout keeps start/goal distinct and off the obstacles."""
    env = GridWorld()
    params = env.default_params()
    _, o_a = env.reset(jax.random.PRNGKey(0), params)
    _, o_b = env.reset(jax.random.PRNGKey(0), params)
    np.testing.assert_array_equal(np.asarray(o_a), np.asarray(o_b))

    layouts = set()
    for i in range(8):
        st, obs = env.reset(jax.random.PRNGKey(i), params)
        obstacles = np.asarray(st.obstacles)
        assert not obstacles[tuple(np.asarray(st.pos))]
        assert not obstacles[tuple(np.asarray(st.goal))]
        assert not np.array_equal(np.asarray(st.pos), np.asarray(st.goal))
        assert int(obstacles.sum()) == params.n_obstacles
        o = np.asarray(obs)
        assert o.shape == (3 * params.size**2,) and o.min() >= 0.0 and o.max() <= 1.0
        layouts.add(obstacles.tobytes() + np.asarray(st.pos).tobytes())
    assert len(layouts) >= 7, "procedural family should vary across keys"


def test_gridworld_truncation_and_fresh_layout_on_reset():
    """The in-graph TimeLimit ends a goal-less crawl at max_episode_steps and
    the auto-reset hands back a (typically different) fresh scenario."""
    env = GridWorld()
    params = env.default_params(max_episode_steps=4)
    step = autoreset_step(env, params)
    key = jax.random.PRNGKey(2)
    key, k0 = jax.random.split(key)
    state, _ = env.reset(k0, params)
    first_goal = np.asarray(state.goal)
    dones = []
    for t in range(4):
        key, ks = jax.random.split(key)
        # walking into the top wall never reaches the goal => pure TimeLimit test
        state, obs, reward, done, info = step(ks, state, jnp.int32(0))
        dones.append(bool(done))
        if not done:
            assert float(reward) == pytest.approx(params.step_penalty)
    assert dones == [False, False, False, True]
    assert int(state.t) == 0, "auto-reset must restart the episode clock"
    # the reset drew a fresh scenario from the key chain (deterministic given seed)
    assert not np.array_equal(np.asarray(state.goal), first_goal)


@pytest.mark.timeout(300)
def test_fused_collect_makes_zero_host_transfers():
    """A warm fused rollout runs to completion under ``jax.transfer_guard``:
    no per-step host pulls, no implicit uploads — the ISSUE's zero-transfer
    guarantee, pinned. The guard is proven live by the explicit pull raising."""
    from sheeprl_tpu.algos.ppo.agent import build_agent
    from sheeprl_tpu.config import load_config
    from sheeprl_tpu.core.runtime import build_runtime
    from sheeprl_tpu.envs import ingraph as ig

    cfg = load_config(
        overrides=[
            "exp=ppo",
            "env=jax_cartpole",
            "env.num_envs=16",
            "algo.rollout_steps=8",
            "algo.mlp_keys.encoder=[state]",
            "algo.cnn_keys.encoder=[]",
        ]
    )
    runtime = build_runtime(cfg.fabric)
    venv = ig.make_vector_env(cfg, 16, 0, device=runtime.device)
    _, _, player = build_agent(runtime, (2,), False, cfg, venv.single_observation_space, None)
    player.params = jax.device_put(player.params, runtime.device)
    venv.reset(seed=0)
    collector = ig.InGraphRolloutCollector(venv, player, rollout_steps=8, gamma=0.99, name="zt")
    collector.collect()  # compile outside the guard
    jax.block_until_ready(venv.carry.obs)

    with jax.transfer_guard("disallow"):
        data, metrics, next_values = collector.collect()
        collector.collect()  # carry chains stay on device across iterations
        jax.block_until_ready(venv.carry.obs)  # fence only — not a transfer
        # sanity that the guard is live: an implicit host->device upload (the
        # python scalar) must raise, so a silent pass above is meaningful
        with pytest.raises(Exception):
            jnp.add(data["rewards"], 1.0)

    rewards = np.asarray(data["rewards"])
    assert rewards.shape == (8, 16, 1)
    assert np.asarray(data[venv.obs_key]).shape == (8, 16, 4)
    assert np.asarray(next_values).shape == (16, 1)
    # CartPole pays 1.0 per step, so every finished episode has return == length
    from sheeprl_tpu.envs.ingraph import iter_finished_episodes

    for ep_ret, ep_len in iter_finished_episodes(metrics):
        assert ep_ret == pytest.approx(float(ep_len))
