"""DreamerV3 RSSM unit tests.

Regression focus: `dynamic_scan` must return *factorized* prior/posterior logits
``[T, B, stoch, discrete]`` — the KL-balance loss softmaxes per categorical over the
discrete dim (reference sheeprl/algos/dreamer_v3/loss.py via
torch.distributions.Independent(OneHotCategorical)); flat ``[T, B, S*D]`` logits
would silently compute one big softmax and reduce over the batch axis too
(only broadcastable — hence undetected — at T==1).
"""

import importlib.util
import os
from types import SimpleNamespace

import gymnasium
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.algos.dreamer_v3.agent import MLPWithHead, RSSM, RecurrentModel, build_agent
from sheeprl_tpu.algos.dreamer_v3.loss import categorical_kl, reconstruction_loss
from sheeprl_tpu.config import compose

KEY = jax.random.PRNGKey(0)

S, D, R, E, A = 3, 4, 8, 6, 2
_TINY = dict(A=A, E=E, DU=8, R=R, HT=8, HR=8, S=S, D=D)


def _make_rssm(decoupled: bool = False, dtype=jnp.float32, dims=_TINY):
    sd, r, e = dims["S"] * dims["D"], dims["R"], dims["E"]
    rec = RecurrentModel(input_size=sd + dims["A"], recurrent_state_size=r, dense_units=dims["DU"], dtype=dtype)
    repr_in = e if decoupled else r + e
    repr_m = MLPWithHead(input_dim=repr_in, hidden_sizes=[dims["HR"]], output_dim=sd, dtype=dtype)
    trans = MLPWithHead(input_dim=r, hidden_sizes=[dims["HT"]], output_dim=sd, dtype=dtype)
    rssm = RSSM(rec, repr_m, trans, stochastic_size=dims["S"], discrete_size=dims["D"], decoupled=decoupled)
    wm_params = {
        "recurrent_model": rec.init(KEY, jnp.zeros((1, sd + dims["A"])), jnp.zeros((1, r))),
        "representation_model": repr_m.init(KEY, jnp.zeros((1, repr_in))),
        "transition_model": trans.init(KEY, jnp.zeros((1, r))),
        "initial_recurrent_state": jnp.zeros((r,), dtype=jnp.float32),
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


# ---- the step against a formulation that shares no code with it ----
#
# `benchmarks/chip/reference/dv3.py` is the chip benchmark's plain reference:
# `jax.numpy` in float32 at `highest`, nothing imported from the program, and it
# names its weights as the program does, so it takes `wm_params` as they are.

_ENV_SHAPES = {
    "cartpole": dict(A=2, E=16, DU=24, R=32, HT=20, HR=28, S=4, D=6),
    "walker_walk": dict(A=6, E=64, DU=48, R=64, HT=48, HR=48, S=8, D=8),
}
_STEP_CASES = pytest.mark.parametrize(
    "shape,dtype",
    [(shape, dtype) for shape in sorted(_ENV_SHAPES) for dtype in (jnp.float32, jnp.bfloat16)],
    ids=[f"{shape}-{dtype}" for shape in sorted(_ENV_SHAPES) for dtype in ("float32", "bfloat16")],
)


@pytest.fixture(scope="module")
def ref():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_dv3", os.path.join(root, "benchmarks", "chip", "reference", "dv3.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _step_case(shape, dtype, B=3):
    """(rssm, wm_params, the reference's sizes, one step's inputs): every leaf
    moved off its initial value, so that no scale of one or bias of zero hides
    a term, and an episode that starts in row 0."""
    dims = _ENV_SHAPES[shape]
    rssm, wm_params = _make_rssm(dtype=dtype, dims=dims)
    leaves, treedef = jax.tree.flatten(wm_params)
    noise = jax.random.split(jax.random.PRNGKey(8), len(leaves))
    wm_params = treedef.unflatten([x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, noise)])
    sizes = {"layer_norm_eps": 1e-3, "discrete_size": dims["D"], "unimix": rssm.unimix}
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    h = 0.2 * jax.random.normal(ks[0], (B, dims["R"]))
    z = jax.nn.one_hot(jax.random.randint(ks[1], (B, dims["S"]), 0, dims["D"]), dims["D"]).reshape(B, -1)
    action = jax.random.normal(ks[2], (B, dims["A"]))
    embedded = jax.random.normal(ks[3], (B, dims["E"]))
    is_first = jnp.zeros((B, 1)).at[0].set(1.0)
    return rssm, wm_params, sizes, (z, h, action, embedded, is_first, ks[4])


def _ref_dynamic_step(ref, wm, sizes, z, h, action, embedded, is_first, key):
    """`RSSM.dynamic_step` written with the reference's functions alone."""
    _, k_post = jax.random.split(key)
    h0 = jnp.broadcast_to(jnp.tanh(wm["initial_recurrent_state"]), h.shape)
    mode = jnp.argmax(ref.prior_logits(wm, h0, sizes, None), axis=-1)
    z0 = jax.nn.one_hot(mode, sizes["discrete_size"]).reshape(z.shape)
    h = (1.0 - is_first) * h + is_first * h0
    z = (1.0 - is_first) * z + is_first * z0
    h = ref.recurrent(wm, z, (1.0 - is_first) * action, h, sizes, None)
    prior = ref.prior_logits(wm, h, sizes, None)
    posterior = ref.posterior_logits(wm, h, embedded, sizes, None)
    return h, ref.gumbel_onehot(k_post, posterior, jnp.float32), posterior, prior


def _assert_close(got, want, dtype, what):
    # float32: two orders of the same sums. bfloat16: the program rounds its
    # activations, the reference none; the band of the fused step's tests
    got, want = np.asarray(got.astype(jnp.float32)), np.asarray(want)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert got.shape == want.shape, what
    assert np.max(np.abs(got - want)) <= tol * max(1.0, np.max(np.abs(want))), what


@_STEP_CASES
def test_dynamic_step_equals_the_plain_reference(ref, shape, dtype):
    rssm, wm_params, sizes, (z, h, action, embedded, is_first, key) = _step_case(shape, dtype)
    cast = lambda x: x.astype(dtype)
    got_h, got_z, _, got_post, got_prior = jax.jit(rssm.dynamic_step)(
        wm_params, cast(z), cast(h), action, cast(embedded), is_first, key
    )
    want_h, want_z, want_post, want_prior = _ref_dynamic_step(ref, wm_params, sizes, z, h, action, embedded, is_first, key)
    B, stoch, discrete = want_post.shape
    _assert_close(got_h, want_h, dtype, "recurrent state")
    _assert_close(got_post.reshape(B, stoch, discrete), want_post, dtype, "posterior logits")
    _assert_close(got_prior.reshape(B, stoch, discrete), want_prior, dtype, "prior logits")
    if dtype == jnp.float32:  # the same key draws the same noise in the same dtype
        np.testing.assert_array_equal(np.argmax(got_z, -1), np.argmax(want_z, -1))


@_STEP_CASES
def test_imagination_step_equals_the_plain_reference(ref, shape, dtype):
    rssm, wm_params, sizes, (z, h, action, _, _, key) = _step_case(shape, dtype)
    got_z, got_h = jax.jit(rssm.imagination_step)(wm_params, z.astype(dtype), h.astype(dtype), action, key)
    want_h = ref.recurrent(wm_params, z, action, h, sizes, None)
    want_prior = ref.prior_logits(wm_params, want_h, sizes, None)
    _assert_close(got_h, want_h, dtype, "recurrent state")
    assert got_z.shape == z.shape
    if dtype == jnp.float32:
        want_z = ref.gumbel_onehot(key, want_prior, jnp.float32)
        np.testing.assert_array_equal(np.argmax(got_z.reshape(want_z.shape), -1), np.argmax(want_z, -1))
    else:  # the noise of the same key in bfloat16: the pick is the reference's, or within the band of its best
        noisy = np.asarray(want_prior + jax.random.gumbel(key, want_prior.shape, jnp.bfloat16).astype(jnp.float32))
        pick = np.argmax(np.asarray(got_z.astype(jnp.float32)).reshape(noisy.shape), -1)
        chosen = np.take_along_axis(noisy, pick[..., None], -1)[..., 0]
        assert np.all(chosen >= noisy.max(-1) - 2 * 5e-2 * max(1.0, np.abs(want_prior).max()))


@pytest.mark.parametrize("shape", sorted(_ENV_SHAPES))
def test_dynamic_scan_gradients_equal_the_plain_reference(ref, shape):
    """Every leaf's gradient of a scalar over T steps, against a `lax.scan` of
    the reference's step: the straight-through samples, the resets and the
    learned initial state are in it."""
    rssm, wm_params, sizes, (_, _, _, _, _, key) = _step_case(shape, jnp.float32)
    dims, T, B = _ENV_SHAPES[shape], 6, 3
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    embedded = jax.random.normal(ks[0], (T, B, dims["E"]))
    actions = jax.random.normal(ks[1], (T, B, dims["A"]))
    is_first = (jax.random.uniform(ks[2], (T, B, 1)) < 0.3).astype(jnp.float32).at[0].set(1.0)
    weights = [
        jax.random.normal(k, shape_)
        for k, shape_ in zip(jax.random.split(ks[3], 4), [(T, B, dims["R"])] + 3 * [(T, B, dims["S"], dims["D"])])
    ]

    def ref_scan(wm):
        def step(carry, xs):
            h, z = carry
            h, sample, posterior, prior = _ref_dynamic_step(ref, wm, sizes, z, h, *xs)
            return (h, sample.reshape(z.shape)), (h, sample, prior, posterior)

        carry = (jnp.zeros((B, dims["R"])), jnp.zeros((B, dims["S"] * dims["D"])))
        return jax.lax.scan(step, carry, (actions, embedded, is_first, jax.random.split(key, T)))[1]

    def loss(outs):
        return sum(jnp.sum(o * w) for o, w in zip(outs, weights))

    got = jax.jit(jax.grad(lambda wm: loss(rssm.dynamic_scan(wm, embedded, actions, is_first, key))))(wm_params)
    want = jax.jit(jax.grad(lambda wm: loss(ref_scan(wm))))(wm_params)
    _assert_grads_close(got, want, jnp.float32)


@pytest.mark.parametrize("decoupled", [False, True], ids=["coupled", "decoupled"])
def test_a_reset_inside_a_sequence_equals_two_sequences(decoupled):
    """``is_first`` at step t cuts the recurrence: the states from t on are
    those of a sequence that starts at t. What a chunked scan has to keep."""
    rssm, wm_params, (embedded, actions, _, key), _ = _scan_case(decoupled, jnp.float32)
    t = 2
    is_first = jnp.zeros((_T, _B, 1)).at[0].set(1.0).at[t].set(1.0)
    keys = jax.random.split(key, _T)

    whole = rssm.dynamic_scan(wm_params, embedded, actions, is_first, key)

    def coupled_step(carry, i):
        h, z = carry
        h, post, _, post_logits, prior_logits = rssm.dynamic_step(
            wm_params, z, h, actions[i], embedded[i], is_first[i], keys[i]
        )
        return (h, post.reshape(_B, -1)), (h, post, prior_logits.reshape(_B, S, D), post_logits.reshape(_B, S, D))

    # decoupled: the posteriors do not depend on the recurrence, so they are the whole scan's
    prev_posts = jnp.concatenate([jnp.zeros_like(whole[1][:1]), whole[1][:-1]]).reshape(_T, _B, -1)
    init_h, init_z = rssm.initial_states(wm_params, (_B,))

    def decoupled_step(carry, i):
        h, first = carry[0], is_first[i]
        h = rssm._recurrent(
            wm_params, (1 - first) * prev_posts[i] + first * init_z, (1 - first) * actions[i], (1 - first) * h + first * init_h
        )
        return (h,), (h, rssm._transition(wm_params, h, keys[i])[0].reshape(_B, S, D))

    def run(lo, hi):
        """Steps lo..hi-1 as a sequence of their own, on the whole scan's keys."""
        carry, out = (jnp.zeros((_B, R)), jnp.zeros((_B, S * D))), []
        for i in range(lo, hi):
            carry, outs = (decoupled_step if decoupled else coupled_step)(carry, i)
            out.append(outs)
        return [jnp.stack(x) for x in zip(*out)]

    compared = (whole[0], whole[2]) if decoupled else whole  # decoupled: recurrent states and prior logits
    for got, first_half, second_half in zip(compared, run(0, t), run(t, _T)):
        np.testing.assert_allclose(np.asarray(got[:t]), np.asarray(first_half), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got[t:]), np.asarray(second_half), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("decoupled", [False, True], ids=["coupled", "decoupled"])
def test_warm_dynamic_scan_makes_no_host_transfer(decoupled):
    """Nothing in the scan brings a Python scalar or a host constant into the
    warm call: an executable compiled ahead runs under `transfer_guard`."""
    from sheeprl_tpu.core import compile as jax_compile

    rssm, wm_params, inputs, _ = _scan_case(decoupled, jnp.float32)
    gfn = jax_compile.guarded_jit(rssm.dynamic_scan, name=f"test.dynamic_scan_{decoupled}")
    args = (wm_params, *inputs)
    gfn.aot_compile(*jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
    args = jax.device_put(args)
    jax.block_until_ready(gfn(*args))
    with jax.transfer_guard("disallow"):
        jax.block_until_ready(gfn(*args))
    assert gfn.stats()["retraces"] == 0 and gfn.stats()["aot_fallbacks"] == 0


# ---- `algo.world_model.kernels`, the removed option ----


def _build_with_kernels(value):
    overrides = ["exp=dreamer_v3", "env=dummy", "algo=dreamer_v3_XS", "algo.cnn_keys.encoder=[]", "algo.cnn_keys.decoder=[]"]
    overrides += ["algo.mlp_keys.encoder=[state]", "algo.mlp_keys.decoder=[state]", f"algo.world_model.kernels={value}"]
    cfg = compose(config_name="config", overrides=overrides)
    obs_space = gymnasium.spaces.Dict({"state": gymnasium.spaces.Box(-1.0, 1.0, (5,), np.float32)})
    runtime = SimpleNamespace(compute_dtype=jnp.float32)
    return build_agent(runtime, (3,), False, cfg, obs_space)


@pytest.mark.parametrize("value", ["pallas", "auto", "reference", "off"])
def test_removed_kernels_key(value):
    """A run that asks for the fused step must not get the flax scan in
    silence; ``off`` is what every saved run's config carries, and builds."""
    if value == "off":
        modules, params, _ = _build_with_kernels(value)
        assert not hasattr(modules.rssm, "kernels") and "world_model" in params
    else:
        with pytest.raises(ValueError, match=r"algo\.world_model\.kernels"):
            _build_with_kernels(value)
