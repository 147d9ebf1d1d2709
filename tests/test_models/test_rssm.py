"""DreamerV3 RSSM unit tests.

Regression focus: `dynamic_scan` must return *factorized* prior/posterior logits
``[T, B, stoch, discrete]`` — the KL-balance loss softmaxes per categorical over the
discrete dim (reference sheeprl/algos/dreamer_v3/loss.py via
torch.distributions.Independent(OneHotCategorical)); flat ``[T, B, S*D]`` logits
would silently compute one big softmax and reduce over the batch axis too
(only broadcastable — hence undetected — at T==1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.algos.dreamer_v3.agent import MLPWithHead, RSSM, RecurrentModel
from sheeprl_tpu.algos.dreamer_v3.loss import categorical_kl, reconstruction_loss

KEY = jax.random.PRNGKey(0)

S, D, R, E, A = 3, 4, 8, 6, 2


def _make_rssm(decoupled: bool = False, dtype=jnp.float32):
    rec = RecurrentModel(input_size=S * D + A, recurrent_state_size=R, dense_units=8, dtype=dtype)
    repr_in = E if decoupled else R + E
    repr_m = MLPWithHead(input_dim=repr_in, hidden_sizes=[8], output_dim=S * D, dtype=dtype)
    trans = MLPWithHead(input_dim=R, hidden_sizes=[8], output_dim=S * D, dtype=dtype)
    rssm = RSSM(rec, repr_m, trans, stochastic_size=S, discrete_size=D, decoupled=decoupled)
    wm_params = {
        "recurrent_model": rec.init(KEY, jnp.zeros((1, S * D + A)), jnp.zeros((1, R))),
        "representation_model": repr_m.init(KEY, jnp.zeros((1, repr_in))),
        "transition_model": trans.init(KEY, jnp.zeros((1, R))),
        "initial_recurrent_state": jnp.zeros((R,), dtype=jnp.float32),
    }
    return rssm, wm_params


@pytest.mark.parametrize("decoupled", [False, True])
def test_dynamic_scan_returns_factorized_logits(decoupled):
    rssm, wm_params = _make_rssm(decoupled)
    T, B = 5, 3
    embedded = jax.random.normal(jax.random.PRNGKey(1), (T, B, E))
    actions = jnp.zeros((T, B, A))
    is_first = jnp.zeros((T, B, 1)).at[0].set(1.0)
    rec_states, posteriors, priors_logits, posteriors_logits = rssm.dynamic_scan(
        wm_params, embedded, actions, is_first, KEY
    )
    assert rec_states.shape == (T, B, R)
    assert posteriors.shape == (T, B, S, D)
    assert priors_logits.shape == (T, B, S, D)
    assert posteriors_logits.shape == (T, B, S, D)
    # KL must stay per-element [T, B] for T > 1 (the T==1 broadcast masked this)
    kl = categorical_kl(posteriors_logits, priors_logits)
    assert kl.shape == (T, B)
    assert bool(jnp.all(kl >= -1e-6))


def test_reconstruction_loss_elementwise_at_t_gt_1():
    rssm, wm_params = _make_rssm()
    T, B = 4, 2
    embedded = jax.random.normal(jax.random.PRNGKey(2), (T, B, E))
    actions = jnp.zeros((T, B, A))
    is_first = jnp.zeros((T, B, 1)).at[0].set(1.0)
    _, _, priors_logits, posteriors_logits = rssm.dynamic_scan(
        wm_params, embedded, actions, is_first, KEY
    )
    po = {"state": jnp.zeros((T, B))}
    loss, kl, state_loss, reward_loss, obs_loss, cont_loss = reconstruction_loss(
        po,
        jnp.zeros((T, B)),
        priors_logits,
        posteriors_logits,
        pc_log_prob=jnp.zeros((T, B)),
    )
    for v in (loss, kl, state_loss, reward_loss, obs_loss, cont_loss):
        assert v.shape == ()
    assert jnp.isfinite(loss)


def test_imagination_step_shapes():
    rssm, wm_params = _make_rssm()
    B = 6
    prior_flat = jnp.zeros((B, S * D))
    rec_state = jnp.zeros((B, R))
    act = jnp.zeros((B, A))
    prior, rec = rssm.imagination_step(wm_params, prior_flat, rec_state, act, KEY)
    assert prior.shape == (B, S * D)
    assert rec.shape == (B, R)
    # one-hot per categorical
    assert jnp.allclose(prior.reshape(B, S, D).sum(-1), 1.0)


def test_dv3_actor_raw_samples_contract():
    """sample_actions_with_raw: the env/dynamics consume CLIPPED actions, the
    score-function estimator evaluates log-prob at the RAW samples (clipping
    rescales saturated continuous samples onto the boundary, where log-prob is
    not the sampled policy's score — benchmarks/WALKER_WALK_NOTES.md)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.algos.dreamer_v3.agent import Actor, ActorOutput

    actor = Actor(
        latent_state_size=8,
        actions_dim=(3,),
        is_continuous=True,
        distribution="auto",
        dense_units=8,
        mlp_layers=1,
    )
    latent = jnp.linspace(-3, 3, 2 * 8).reshape(2, 8)
    params = actor.init(jax.random.PRNGKey(0), latent)
    out = ActorOutput(actor, actor.apply(params, latent))
    (clipped,), (raw,) = out.sample_actions_with_raw(jax.random.PRNGKey(1))
    assert clipped.shape == raw.shape == (2, 3)
    # clipped action is the clip-rescaled raw sample; inside the box they agree
    np.testing.assert_allclose(
        np.asarray(clipped), np.clip(np.asarray(raw), -1.0, 1.0) * 0 + np.asarray(raw) * np.minimum(1.0, 1.0 / np.abs(np.asarray(raw))), rtol=1e-5
    )
    assert np.all(np.abs(np.asarray(clipped)) <= 1.0 + 1e-6)
    # sample_actions returns exactly the clipped list
    (via_plain,) = ActorOutput(actor, actor.apply(params, latent)).sample_actions(jax.random.PRNGKey(1))
    np.testing.assert_allclose(np.asarray(via_plain), np.asarray(clipped), rtol=1e-6)
    # discrete: raw == clipped (one-hot samples)
    dactor = Actor(
        latent_state_size=8, actions_dim=(4,), is_continuous=False, distribution="auto",
        dense_units=8, mlp_layers=1,
    )
    dparams = dactor.init(jax.random.PRNGKey(0), latent)
    dout = ActorOutput(dactor, dactor.apply(dparams, latent))
    (dc,), (dr,) = dout.sample_actions_with_raw(jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(dc), np.asarray(dr))


# ---- the weight gradients of the dynamic scan are taken outside the scan ----
#
# `RSSM.dynamic_scan` against `_plain_dynamic_scan`, the scan as it was before
# the kernels' gradients left it: a plain `lax.scan` whose step closes over
# `wm_params`, so that JAX's transpose sums every kernel's gradient in the
# backward scan's carry. Same forward, bit for bit; same gradients to rounding.

_SCAN_CASES = pytest.mark.parametrize(
    "decoupled,dtype",
    [(False, jnp.float32), (False, jnp.bfloat16), (True, jnp.float32), (True, jnp.bfloat16)],
    ids=["coupled-float32", "coupled-bfloat16", "decoupled-float32", "decoupled-bfloat16"],
)
_T, _B = 5, 4


def _plain_dynamic_scan(rssm, wm_params, embedded, actions, is_first, key):
    T, B = embedded.shape[:2]
    keys = jax.random.split(key, T)
    init_rec = jnp.zeros((B, R), dtype=embedded.dtype)
    init_post = jnp.zeros((B, S * D), dtype=embedded.dtype)
    if rssm.decoupled:
        post_keys = jax.random.split(jax.random.fold_in(key, 1), T)
        posteriors_logits, posteriors = jax.vmap(lambda e, k: rssm._representation(wm_params, e, k))(
            embedded, post_keys
        )
        flat = posteriors.reshape(T, B, -1)
        prev_posts = jnp.concatenate([jnp.zeros_like(flat[:1]), flat[:-1]], axis=0)

        def step(h, xs):
            prev_post, action, is_f, k = xs
            action = (1 - is_f) * action
            init_r, init_p = rssm.initial_states(wm_params, h.shape[:-1])
            h = (1 - is_f) * h + is_f * init_r
            prev_post = (1 - is_f) * prev_post + is_f * init_p
            h = rssm._recurrent(wm_params, prev_post, action, h)
            prior_logits, _ = rssm._transition(wm_params, h, k)
            return h, (h, prior_logits)

        _, (recurrent_states, priors_logits) = jax.lax.scan(step, init_rec, (prev_posts, actions, is_first, keys))
    else:

        def step(carry, xs):
            h, z = carry
            action, e, is_f, k = xs
            h, posterior, _, post_logits, prior_logits = rssm.dynamic_step(wm_params, z, h, action, e, is_f, k)
            return (h, posterior.reshape(B, -1)), (h, posterior, post_logits, prior_logits)

        _, (recurrent_states, posteriors, posteriors_logits, priors_logits) = jax.lax.scan(
            step, (init_rec, init_post), (actions, embedded, is_first, keys)
        )
    return (
        recurrent_states,
        posteriors,
        priors_logits.reshape(T, B, S, D),
        posteriors_logits.reshape(T, B, S, D),
    )


def _scan_case(decoupled, dtype):
    """(rssm, wm_params, inputs, loss): episodes that start inside the sequence,
    a learned initial state that is not zero, and a scalar of all four outputs."""
    rssm, wm_params = _make_rssm(decoupled, dtype)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    wm_params["initial_recurrent_state"] = 0.3 * jax.random.normal(ks[0], (R,))
    embedded = jax.random.normal(ks[1], (_T, _B, E)).astype(dtype)
    actions = jax.random.normal(ks[2], (_T, _B, A))
    is_first = (jax.random.uniform(ks[3], (_T, _B, 1)) < 0.25).astype(jnp.float32).at[0].set(1.0)
    inputs = (embedded, actions, is_first, KEY)
    shapes = [(_T, _B, R), (_T, _B, S, D), (_T, _B, S, D), (_T, _B, S, D)]
    weights = [jax.random.normal(jax.random.PRNGKey(20 + i), shape) for i, shape in enumerate(shapes)]

    def loss(scan, wm_params, *inputs):
        outs = scan(rssm, wm_params, *inputs)
        return sum(jnp.sum(o.astype(jnp.float32) * w) for o, w in zip(outs, weights))

    return rssm, wm_params, inputs, loss


def _assert_grads_close(got, want, dtype):
    # float32: rounding of a sum taken in another order. bfloat16: the plain scan
    # rounds each step's [in, out] product to bfloat16 before it is added, the
    # contraction over [T, B] rounds once, so they differ by that rounding
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert np.linalg.norm(w) > 0, jax.tree_util.keystr(path)
        assert np.linalg.norm(g - w) <= tol * np.linalg.norm(w), jax.tree_util.keystr(path)


@_SCAN_CASES
def test_dynamic_scan_forward_is_bitwise_the_plain_scan(decoupled, dtype):
    rssm, wm_params, inputs, _ = _scan_case(decoupled, dtype)
    got = jax.jit(rssm.dynamic_scan)(wm_params, *inputs)
    want = jax.jit(lambda *a: _plain_dynamic_scan(rssm, *a))(wm_params, *inputs)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)), np.asarray(w.astype(jnp.float32)))


@_SCAN_CASES
def test_dynamic_scan_gradients_equal_the_plain_scans(decoupled, dtype):
    """Every leaf of ``wm_params``: the kernels that left the carry, and the
    LayerNorm leaves, biases and ``initial_recurrent_state`` that stayed."""
    _, wm_params, inputs, loss = _scan_case(decoupled, dtype)
    got = jax.jit(jax.grad(lambda p, *i: loss(RSSM.dynamic_scan, p, *i)))(wm_params, *inputs)
    want = jax.jit(jax.grad(lambda p, *i: loss(_plain_dynamic_scan, p, *i)))(wm_params, *inputs)
    _assert_grads_close(got, want, dtype)


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scans(sub)


def _kernel_shaped_scan_carries(grad_fn, wm_params, inputs):
    """The carries of every ``scan`` of the gradient's program that have the
    shape of a dense kernel of ``wm_params`` (a kernel the step only reads is a
    constant of the scan, not a carry)."""
    kernels = {
        leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(wm_params)
        if "kernel" in jax.tree_util.keystr(path)
    }
    scans = list(_scans(jax.make_jaxpr(grad_fn)(wm_params, *inputs).jaxpr))
    assert len(scans) >= 2, "no forward and backward scan in the gradient: the reading would be vacuous"
    carried = [
        v.aval.shape
        for eqn in scans
        for v in eqn.invars[eqn.params["num_consts"] : eqn.params["num_consts"] + eqn.params["num_carry"]]
    ]
    return sorted(kernels.intersection(carried))


@_SCAN_CASES
def test_dynamic_scan_backward_carries_no_kernel(decoupled, dtype):
    """What keeps a refactoring from putting the sum back: no scan of the
    gradient's program carries anything of a dense kernel's shape. The plain
    scan's backward does (else the reading reads nothing)."""
    _, wm_params, inputs, loss = _scan_case(decoupled, dtype)
    assert _kernel_shaped_scan_carries(jax.grad(lambda p, *i: loss(_plain_dynamic_scan, p, *i)), wm_params, inputs)
    assert not _kernel_shaped_scan_carries(jax.grad(lambda p, *i: loss(RSSM.dynamic_scan, p, *i)), wm_params, inputs)


@pytest.mark.parametrize("decoupled", [False, True], ids=["coupled", "decoupled"])
def test_dynamic_scan_gradients_with_the_batch_sharded(decoupled):
    """Data parallelism: the contraction over [T, B] is over the sharded batch
    axis, and GSPMD reduces it as it reduces every other gradient."""
    _, wm_params, inputs, loss = _scan_case(decoupled, jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    replicated, by_batch = NamedSharding(mesh, P()), NamedSharding(mesh, P(None, "data"))
    embedded, actions, is_first, key = inputs
    sharded = (*(jax.device_put(x, by_batch) for x in (embedded, actions, is_first)), jax.device_put(key, replicated))
    grad = jax.jit(jax.grad(lambda p, *i: loss(RSSM.dynamic_scan, p, *i)), out_shardings=replicated)
    got = grad(jax.device_put(wm_params, replicated), *sharded)
    assert all(len(g.sharding.device_set) == 2 for g in jax.tree.leaves(got))
    want = jax.jit(jax.grad(lambda p, *i: loss(_plain_dynamic_scan, p, *i)))(wm_params, *inputs)
    _assert_grads_close(got, want, jnp.float32)
