"""Faults planted under the timed path, to show that ``correct`` comes out false.
`calibrate.py` reads them on the chip; the tests drive them on the CPU. Each
wraps the program's train function and keeps its signature."""

from __future__ import annotations


def half_batch(train_fn):
    """Half of the batch left out, the mean taken over the rest: the second half
    of every batch is overwritten with the first, so every mean is over B/2 rows."""
    import jax
    import jax.numpy as jnp

    def wrapped(params, opt_states, moments, counter, batches, key):
        def halve(x):
            h = x.shape[2] // 2
            return jnp.concatenate([x[:, :, :h], x[:, :, :h]], axis=2)

        return train_fn(params, opt_states, moments, counter, jax.tree_util.tree_map(halve, batches), key)

    return wrapped


def state_unchanged(train_fn):
    """A step that returns its state as it got it (the counter still advances)."""
    import jax
    import jax.numpy as jnp

    def wrapped(params, opt_states, moments, counter, batches, key):
        keep = jax.tree_util.tree_map(jnp.copy, (params, opt_states, moments))  # the call donates its inputs
        out = train_fn(params, opt_states, moments, counter, batches, key)
        return (*keep, *out[3:])

    return wrapped


FAULTS = {"half_batch": half_batch, "state_unchanged": state_unchanged}
