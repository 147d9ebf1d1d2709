"""Fused Pallas RSSM step kernels (``sheeprl_tpu/ops/pallas/rssm_step.py``).

The contract under test, at two environment-shaped sizes (a CartPole-ish small
config and a walker_walk-ish one):

* ``interpret`` (the Pallas kernel run through the interpreter) is BITWISE
  equal to ``reference`` (the same fused formulation in plain jnp) — the CPU
  proof that the kernel body computes the reference math.
* the hand-written ``custom_vjp`` matches autodiff of the same forward
  (tight in f32, atol-tiered for bf16 — the backward recompute re-rounds).
* dispatch: ``kernels=off`` is the untouched flax path, the
  ``train.kernel_dispatch`` failpoint degrades the fused path to output
  bitwise equal to flax, a named ``pallas`` beyond the VMEM gate raises (never
  another implementation in silence), and unsupported parameter structures
  raise :class:`KernelUnsupported`.
* a warmed fused scan dispatches with zero host transfers
  (``jax.transfer_guard``): nothing in the fused path smuggles a Python
  scalar or host constant into the steady-state step.
"""

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.algos.dreamer_v3.agent import MLPWithHead, RecurrentModel, RSSM
from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.core import failpoints
from sheeprl_tpu.ops.pallas import rssm_step as K

pytestmark = pytest.mark.kernels

# env-shaped dims (scaled to CPU-test size; ratios mirror the real configs)
SHAPES = {
    "cartpole": dict(A=2, E=16, DU=24, R=32, HT=20, HR=28, S=4, D=6),
    "walker_walk": dict(A=6, E=64, DU=48, R=64, HT=48, HR=48, S=8, D=8),
}


def _spec(dims, dtype="float32", impl="reference"):
    return K.RSSMStepSpec(
        action_size=dims["A"],
        embed_size=dims["E"],
        dense_units=dims["DU"],
        recurrent_size=dims["R"],
        trans_hidden=dims["HT"],
        repr_hidden=dims["HR"],
        stochastic=dims["S"],
        discrete=dims["D"],
        unimix=0.01,
        eps_in=1e-3,
        eps_gru=1e-3,
        eps_trans=1e-3,
        eps_repr=1e-3,
        dtype=dtype,
        impl=impl,
    )


def _raw_params(dims, key):
    A, E, DU, R = dims["A"], dims["E"], dims["DU"], dims["R"]
    HT, HR, SD = dims["HT"], dims["HR"], dims["S"] * dims["D"]
    ks = jax.random.split(key, 13)
    f32 = jnp.float32
    return {
        "wi_z": jax.random.normal(ks[0], (SD, DU), f32) * 0.1,
        "wi_a": jax.random.normal(ks[1], (A, DU), f32) * 0.1,
        "ln_i_scale": jnp.ones((DU,), f32) + 0.05 * jax.random.normal(ks[2], (DU,)),
        "ln_i_bias": 0.05 * jax.random.normal(ks[3], (DU,)),
        "wg_h": jax.random.normal(ks[4], (R, 3 * R), f32) * 0.1,
        "wg_f": jax.random.normal(ks[5], (DU, 3 * R), f32) * 0.1,
        "ln_g_scale": jnp.ones((3 * R,), f32),
        "ln_g_bias": jnp.zeros((3 * R,), f32),
        "wt": jax.random.normal(ks[6], (R, HT), f32) * 0.1,
        "ln_t_scale": jnp.ones((HT,), f32),
        "ln_t_bias": jnp.zeros((HT,), f32),
        "wt_head": jax.random.normal(ks[7], (HT, SD), f32) * 0.1,
        "bt_head": 0.01 * jax.random.normal(ks[8], (SD,)),
        "wr_h": jax.random.normal(ks[9], (R, HR), f32) * 0.1,
        "wr_e": jax.random.normal(ks[10], (E, HR), f32) * 0.1,
        "ln_r_scale": jnp.ones((HR,), f32),
        "ln_r_bias": jnp.zeros((HR,), f32),
        "wr_head": jax.random.normal(ks[11], (HR, SD), f32) * 0.1,
        "br_head": 0.01 * jax.random.normal(ks[12], (SD,)),
    }


def _scan_data(dims, key, T=5, B=3):
    ks = jax.random.split(key, 5)
    f32 = jnp.float32
    init_raw = jax.random.normal(ks[0], (dims["R"],), f32) * 0.3
    emb = jax.random.normal(ks[1], (T, B, dims["E"]), f32)
    act = jax.random.normal(ks[2], (T, B, dims["A"]), f32)
    isf = (jax.random.uniform(ks[3], (T, B, 1)) < 0.3).astype(f32).at[0].set(1.0)
    return init_raw, emb, act, isf, ks[4]


def _rel_err(tree_a, tree_b):
    worst = 0.0
    for x, y in zip(jax.tree_util.tree_leaves(tree_a), jax.tree_util.tree_leaves(tree_b)):
        x32, y32 = x.astype(jnp.float32), y.astype(jnp.float32)
        d = float(jnp.max(jnp.abs(x32 - y32)))
        worst = max(worst, d / (float(jnp.max(jnp.abs(y32))) + 1e-8))
    return worst


# --------------------------------------------------------------------------- #
# bit-parity: interpret kernel vs reference formulation
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_dynamic_scan_interpret_is_bitwise_vs_reference(shape):
    dims = SHAPES[shape]
    spec = _spec(dims)
    p = _raw_params(dims, jax.random.PRNGKey(0))
    init_raw, emb, act, isf, skey = _scan_data(dims, jax.random.PRNGKey(1))
    ref = K.fused_dynamic_scan(p, spec, init_raw, emb, act, isf, skey)
    itp = K.fused_dynamic_scan(p, spec.with_impl("interpret"), init_raw, emb, act, isf, skey)
    for name, r, i in zip(("h", "z", "prior_logits", "post_logits"), ref, itp):
        assert bool(jnp.all(r == i)), f"{name} not bitwise between interpret and reference"


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_imagination_step_interpret_is_bitwise_vs_reference(shape):
    dims = SHAPES[shape]
    spec = _spec(dims)
    p = _raw_params(dims, jax.random.PRNGKey(2))
    B, SD = 4, dims["S"] * dims["D"]
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    h = jax.random.normal(ks[0], (B, dims["R"]), jnp.float32) * 0.2
    z = jax.nn.one_hot(
        jax.random.randint(ks[1], (B, dims["S"]), 0, dims["D"]), dims["D"]
    ).reshape(B, SD)
    a = jax.random.normal(ks[2], (B, dims["A"]), jnp.float32)
    # jit both sides: eager dispatch and compiled code differ by FMA fusion
    o_ref = jax.jit(lambda: K.fused_imagination_step(p, spec, z, h, a, ks[3]))()
    o_itp = jax.jit(lambda: K.fused_imagination_step(p, spec.with_impl("interpret"), z, h, a, ks[3]))()
    assert bool(jnp.all(o_ref[0] == o_itp[0]))
    assert bool(jnp.all(o_ref[1] == o_itp[1]))
    assert o_ref[0].shape == (B, SD)  # flat prior, the flax contract


# --------------------------------------------------------------------------- #
# gradient parity: hand-written custom_vjp vs autodiff of the same forward
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize(
    "dtype,tol",
    [
        ("float32", 1e-4),
        # bf16 movement re-rounds the backward recompute; the f32 islands keep
        # the error bounded but not tight
        ("bfloat16", 5e-2),
    ],
)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_dynamic_scan_grad_parity(shape, dtype, tol):
    dims = SHAPES[shape]
    spec = _spec(dims, dtype=dtype)
    p = _raw_params(dims, jax.random.PRNGKey(4))
    init_raw, emb, act, isf, skey = _scan_data(dims, jax.random.PRNGKey(5))
    Dn = dims["D"]

    def loss(pp, ir, use_custom_vjp):
        h, z, pl, ql = K.fused_dynamic_scan(
            pp, spec, ir, emb, act, isf, skey, use_custom_vjp=use_custom_vjp
        )
        h, z = h.astype(jnp.float32), z.astype(jnp.float32)
        pl, ql = pl.astype(jnp.float32), ql.astype(jnp.float32)
        return (
            jnp.sum(h * h) * 0.1
            + jnp.sum(z * jnp.arange(Dn, dtype=jnp.float32))
            + jnp.sum(jax.nn.softmax(pl) * ql)
            + jnp.sum(pl * 0.01)
        )

    g_custom = jax.grad(loss, argnums=(0, 1))(p, init_raw, True)
    g_auto = jax.grad(loss, argnums=(0, 1))(p, init_raw, False)
    assert _rel_err(g_custom, g_auto) < tol


def test_imagination_grad_parity():
    dims = SHAPES["walker_walk"]
    spec = _spec(dims)
    p = _raw_params(dims, jax.random.PRNGKey(6))
    B, S, Dn = 3, dims["S"], dims["D"]
    SD = S * Dn
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    h = jax.random.normal(ks[0], (B, dims["R"]), jnp.float32) * 0.2
    z = jax.nn.one_hot(jax.random.randint(ks[1], (B, S), 0, Dn), Dn).reshape(B, SD)
    a = jax.random.normal(ks[2], (B, dims["A"]), jnp.float32)
    ik = ks[3]

    def loss_custom(pp, hh):
        zp, hn = K.fused_imagination_step(pp, spec, z, hh, a, ik)
        return jnp.sum(hn * hn) + jnp.sum(zp * 0.3)

    def loss_auto(pp, hh):
        (hn, zn), _ = K._imag_math(pp, spec, hh, z, a, jax.random.gumbel(ik, (B, S, Dn), jnp.float32))
        return jnp.sum(hn * hn) + jnp.sum(zn.reshape(B, SD) * 0.3)

    g1 = jax.grad(loss_custom, argnums=(0, 1))(p, h)
    g2 = jax.grad(loss_auto, argnums=(0, 1))(p, h)
    assert _rel_err(g1, g2) < 1e-4


# --------------------------------------------------------------------------- #
# flax parity + dispatch through RSSM
# --------------------------------------------------------------------------- #


def _flax_rssm(dims, kernels):
    rm = RecurrentModel(
        input_size=dims["A"] + dims["S"] * dims["D"],
        recurrent_state_size=dims["R"],
        dense_units=dims["DU"],
        layer_norm=True,
        layer_norm_eps=1e-3,
    )
    rep = MLPWithHead(
        input_dim=dims["E"] + dims["R"],
        hidden_sizes=[dims["HR"]],
        output_dim=dims["S"] * dims["D"],
        activation="silu",
        layer_norm=True,
        layer_norm_eps=1e-3,
    )
    trans = MLPWithHead(
        input_dim=dims["R"],
        hidden_sizes=[dims["HT"]],
        output_dim=dims["S"] * dims["D"],
        activation="silu",
        layer_norm=True,
        layer_norm_eps=1e-3,
    )
    return RSSM(
        rm, rep, trans, stochastic_size=dims["S"], discrete_size=dims["D"],
        unimix=0.01, kernels=kernels,
    )


def _flax_params(rssm, dims, key):
    B = 3
    k1, k2, k3, k4 = jax.random.split(key, 4)
    SD = dims["S"] * dims["D"]
    return {
        "recurrent_model": rssm.recurrent_model.init(
            k1, jnp.zeros((B, dims["A"] + SD)), jnp.zeros((B, dims["R"]))
        ),
        "representation_model": rssm.representation_model.init(
            k2, jnp.zeros((B, dims["E"] + dims["R"]))
        ),
        "transition_model": rssm.transition_model.init(k3, jnp.zeros((B, dims["R"]))),
        "initial_recurrent_state": 0.3 * jax.random.normal(k4, (dims["R"],)),
    }


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_fused_step_math_matches_flax_single_step(shape):
    """Given identical inputs, one fused step reproduces flax's dynamic_step to
    float rounding (the scan trajectories then diverge only through sampling)."""
    dims = SHAPES[shape]
    SD = dims["S"] * dims["D"]
    rssm = _flax_rssm(dims, "off")
    wm_params = _flax_params(rssm, dims, jax.random.PRNGKey(8))
    B = 3
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    h_in = jax.random.normal(ks[0], (B, dims["R"])) * 0.2
    z_in = jax.nn.one_hot(
        jax.random.randint(ks[1], (B, dims["S"]), 0, dims["D"]), dims["D"]
    ).reshape(B, SD)
    a = jax.random.normal(ks[2], (B, dims["A"]))
    e = jax.random.normal(ks[3], (B, dims["E"]))
    f = jnp.zeros((B, 1))
    fh, _, _, fpost_l, fprior_l = rssm.dynamic_step(wm_params, z_in, h_in, a, e, f, ks[4])

    spec = _flax_rssm(dims, "reference")._fused_spec(dims["E"], dims["A"])
    p = K.extract_step_params(wm_params, SD)
    ih, iz = K.initial_step_states(p, spec, wm_params["initial_recurrent_state"], B)
    g = jax.random.gumbel(ks[5], (B, dims["S"], dims["D"]), jnp.float32)
    (mh, _, mpost_l, mprior_l), _ = K._dyn_math(p, spec, ih, iz, h_in, z_in, a, e, f, g)

    def _close(x, y):
        return float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))) < 2e-5

    assert _close(fh, mh)
    assert _close(fprior_l.reshape(B, dims["S"], dims["D"]), mprior_l)
    assert _close(fpost_l.reshape(B, dims["S"], dims["D"]), mpost_l)
    # hoisted initial states: h is bitwise (same tanh), z is one softmax apart
    fih, fiz = rssm.initial_states(wm_params, (B,))
    assert bool(jnp.all(fih == ih))
    assert _close(fiz, iz)


def test_kernels_off_is_the_untouched_flax_path():
    """``kernels=off`` must route through flax code only — outputs at every
    shape match a dispatch-free RSSM bitwise (the seed-behavior guarantee)."""
    dims = SHAPES["cartpole"]
    rssm_off = _flax_rssm(dims, "off")
    wm_params = _flax_params(rssm_off, dims, jax.random.PRNGKey(10))
    init_raw, emb, act, isf, skey = _scan_data(dims, jax.random.PRNGKey(11))
    out_off = rssm_off.dynamic_scan(wm_params, emb, act, isf, skey)
    out_ref = _flax_rssm(dims, "reference").dynamic_scan(wm_params, emb, act, isf, skey)
    # same contract (shapes/dtypes), different sampling streams
    for a_, b_ in zip(out_off, out_ref):
        assert a_.shape == b_.shape and a_.dtype == b_.dtype


def test_kernel_dispatch_failpoint_degrades_to_flax_bitwise():
    dims = SHAPES["cartpole"]
    rssm_ref = _flax_rssm(dims, "reference")
    rssm_off = _flax_rssm(dims, "off")
    wm_params = _flax_params(rssm_off, dims, jax.random.PRNGKey(12))
    _, emb, act, isf, skey = _scan_data(dims, jax.random.PRNGKey(13))
    out_off = rssm_off.dynamic_scan(wm_params, emb, act, isf, skey)
    failpoints.configure("train.kernel_dispatch:fire")
    try:
        out_fp = rssm_ref.dynamic_scan(wm_params, emb, act, isf, skey)
    finally:
        failpoints.reset()
    for name, a_, b_ in zip(("h", "z", "prior_l", "post_l"), out_fp, out_off):
        assert bool(jnp.all(a_ == b_)), f"failpoint path must equal flax path ({name})"


# --------------------------------------------------------------------------- #
# dispatch units: select_impl, VMEM gate, extract_step_params
# --------------------------------------------------------------------------- #


def test_select_impl_knob_resolution():
    dims = SHAPES["cartpole"]
    spec = _spec(dims)
    assert K.select_impl("off", spec, 4) is None
    assert K.select_impl("reference", spec, 4) == "reference"
    assert K.select_impl("interpret", spec, 4) == "interpret"
    assert K.select_impl("auto", spec, 4, platform="cpu") == "reference"
    # Mosaic refuses the kernel (PR 22 chip run): auto must not pick it on TPU
    assert K.select_impl("auto", spec, 4, platform="tpu") == "reference"
    assert K.select_impl("pallas", spec, 4, platform="tpu") == "pallas"
    with pytest.raises(ValueError):
        K.select_impl("turbo", spec, 4)


def test_select_impl_named_pallas_over_budget_raises(monkeypatch):
    """A named implementation is what runs or the call raises: never another
    implementation in silence."""
    dims = SHAPES["cartpole"]
    spec = _spec(dims)
    monkeypatch.setenv("SHEEPRL_TPU_KERNEL_VMEM_BUDGET", "1024")  # nothing fits
    with pytest.raises(ValueError, match="VMEM"):
        K.select_impl("pallas", spec, 4, platform="tpu")
    assert K.select_impl("auto", spec, 4, platform="tpu") == "reference"
    monkeypatch.setenv("SHEEPRL_TPU_KERNEL_VMEM_BUDGET", str(1 << 40))
    assert K.select_impl("pallas", spec, 4, platform="tpu") == "pallas"


def test_step_vmem_bytes_scales_with_batch_and_dtype():
    dims = SHAPES["walker_walk"]
    f32 = _spec(dims, dtype="float32")
    bf16 = _spec(dims, dtype="bfloat16")
    assert K.step_vmem_bytes(f32, 64) > K.step_vmem_bytes(f32, 8)
    # params are stored f32 whatever the compute dtype; bf16 adds the cast copies
    assert K.step_vmem_bytes(bf16, 8) > K.step_vmem_bytes(f32, 8)


def test_extract_step_params_rejects_unsupported_structures():
    dims = SHAPES["cartpole"]
    rssm = _flax_rssm(dims, "off")
    wm_params = _flax_params(rssm, dims, jax.random.PRNGKey(14))
    SD = dims["S"] * dims["D"]
    p = K.extract_step_params(wm_params, SD)
    assert set(p) == set(K.PARAM_KEYS)

    # a bias on the recurrent projection means layer_norm was off -> unsupported
    import copy

    broken = copy.deepcopy(jax.tree.map(lambda x: x, wm_params))
    dense = broken["recurrent_model"]["params"]["MLP_0"]["Dense_0"]
    dense["bias"] = jnp.zeros((dims["DU"],))
    with pytest.raises(K.KernelUnsupported):
        K.extract_step_params(broken, SD)

    # a second trunk layer is outside the fused single-layer contract
    broken2 = jax.tree.map(lambda x: x, wm_params)
    broken2["transition_model"]["params"]["MLP_0"] = dict(
        broken2["transition_model"]["params"]["MLP_0"]
    )
    broken2["transition_model"]["params"]["MLP_0"]["Dense_1"] = {
        "kernel": jnp.zeros((dims["HT"], dims["HT"]))
    }
    with pytest.raises(K.KernelUnsupported):
        K.extract_step_params(broken2, SD)


# --------------------------------------------------------------------------- #
# zero-host-transfer proof for the warmed fused scan
# --------------------------------------------------------------------------- #


def test_warm_fused_scan_makes_zero_host_transfers():
    dims = SHAPES["cartpole"]
    spec = _spec(dims)
    p = _raw_params(dims, jax.random.PRNGKey(15))
    init_raw, emb, act, isf, skey = _scan_data(dims, jax.random.PRNGKey(16))

    def scan(pp, ir, e_, a_, f_, k_):
        return K.fused_dynamic_scan(pp, spec, ir, e_, a_, f_, k_)

    gfn = jax_compile.guarded_jit(scan, name="test.fused_scan")
    args = (p, init_raw, emb, act, isf, skey)
    gfn.aot_compile(*jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
    args = jax.device_put(args)
    jax.block_until_ready(gfn(*args))  # first dispatch through the AOT executable
    with jax.transfer_guard("disallow"):
        out = gfn(*args)
        jax.block_until_ready(out)  # fence only — not a transfer
