"""Faults planted under the timed path of a token-policy cell, to show that
``correct`` comes out false. `calibrate_seq.py` reads them on the chip; the tests
drive them on the CPU. Each takes what the driver built and returns a train
function with the program's signature."""

from __future__ import annotations

import dataclasses


def _train_fn(built, agent):
    from sheeprl_tpu.algos.ppo_recurrent import ppo_recurrent

    cfg = built["cfg"]
    obs_keys = list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
    return ppo_recurrent.make_train_fn(agent, built["tx"], cfg, built["runtime"], obs_keys, list(cfg.algo.cnn_keys.encoder), None)


def half_batch(built):
    """Half of the batch left out: the second half of the sequences is overwritten with the first."""
    import jax
    import jax.numpy as jnp

    train_fn = built["sound_train_fn"]

    def wrapped(params, opt_state, data, *rest):
        def halve(x):
            h = x.shape[1] // 2
            return jnp.concatenate([x[:, :h], x[:, :h]], axis=1)

        return train_fn(params, opt_state, jax.tree_util.tree_map(halve, data), *rest)

    return wrapped


def state_unchanged(built):
    """A step that returns its state as it got it. The state it got is always the seed's (no step ever
    changed it), so it is made again from the seed after the call: a copy kept across the call would
    be 6 GB beside a program that needs the room (my chip run, PR 29: RESOURCE_EXHAUSTED)."""
    import jax.numpy as jnp

    train_fn, runtime = built["sound_train_fn"], built["runtime"]

    def wrapped(params, opt_state, *rest):
        metrics = train_fn(params, opt_state, *rest)[2:]  # the new state is dropped
        del params, opt_state
        params = runtime.place_params(built["make_weights"](jnp.int32(built["seed32"])))
        return (params, runtime.place_params(built["tx"].init(params)), *metrics)

    return wrapped


def expert_left_out(built, expert: int = 3):
    """One held expert's output left out, in every expert layer: its down projection reads as zero."""
    import jax

    agent = built["agent"]

    class Faulty(type(agent)):
        def evaluate(self, params, batch, norm_obs):
            def mask(path, w):
                if getattr(path[-1], "key", None) == "w2" and w.ndim == 3:
                    return w.at[expert].set(0.0)
                return w

            return super().evaluate(jax.tree_util.tree_map_with_path(mask, params), batch, norm_obs)

    return _train_fn(built, Faulty(agent.config, agent.dtype))


def three_experts(built):
    """Three experts a token instead of four (one fewer than the configuration's, whatever it is)."""
    agent = built["agent"]
    config = dataclasses.replace(agent.config, num_experts_per_tok=agent.config.num_experts_per_tok - 1)
    return _train_fn(built, type(agent)(config, agent.dtype))


FAULTS = {
    "half_batch": half_batch, "state_unchanged": state_unchanged,
    "expert_left_out": expert_left_out, "three_experts": three_experts,
}
