"""The language-model policy's blocks (``sheeprl_tpu/models/lm.py``) against the plain
reference (``benchmarks/chip/reference/lfm2_ppo.py``, float32, nothing imported from the
program), at small sizes on the CPU: each part, the whole pass, the chip's share of an
expert layer, and acting through the carried state."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
import common  # noqa: E402

from sheeprl_tpu.models import lm  # noqa: E402

ref = common.load_module("reference", "lfm2_ppo")

LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv"]
SIZES = dict(
    hidden_size=32, layers=[0, 2, 3, 4, 5], layer_types=LAYER_TYPES, num_dense_layers=2, intermediate_size=48,
    moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2, experts_held=8, expert_lo=0, vocab=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, rope_theta=1e6, norm_eps=1e-5, conv_L_cache=3,
    norm_topk_prob=True, routed_scaling_factor=1.0, query_block=8,
)
B, T = 2, 32
TOL = 2e-5  # float32 on both sides; the orders of summation differ


def config(**kw):
    base = dict(
        hidden_size=32, layers=(0, 2, 3, 4, 5), layer_types=tuple(LAYER_TYPES), num_dense_layers=2, intermediate_size=48,
        moe_intermediate_size=24, num_experts=8, num_experts_per_tok=2, experts_held=8, vocab_held=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8, max_positions=T, query_block=8, head_chunk=8,
    )
    base.update(kw)
    return lm.LMConfig(**base)


@pytest.fixture(scope="module")
def uncut():
    s = ref.sizes_from(SIZES)
    return s, ref.make_params(ref.param_spec(s), 11)


def _close(a, b, tol=TOL):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol)


@pytest.mark.parametrize("part", ["conv", "attn", "dense_ffn", "moe"])
def test_each_part_equals_the_reference(uncut, part):
    s, params = uncut
    cfg = config()
    n = jax.random.normal(jax.random.PRNGKey(1), (B, T, 32))
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        if part == "conv":
            _close(lm.conv_op(layers["layer_0"]["conv"], n)[0], ref.conv_op(layers["layer_0"]["conv"], n, s, None))
        elif part == "attn":
            _close(lm.attn_op(layers["layer_1"]["attn"], n, cfg)[0], ref.attn_op(layers["layer_1"]["attn"], n, s, None))
        elif part == "dense_ffn":
            p = layers["layer_0"]["ffn"]
            _close(lm.gated_mlp(p, n), ref.gated_mlp(n, p["w1"], p["w3"], p["w2"], None))
        else:
            flat = n.reshape(-1, 32)
            out, chosen, counters = lm.moe_ffn(layers["layer_2"]["moe"], flat, cfg)
            want, want_chosen = ref.moe_ffn(layers["layer_2"]["moe"], flat, s, None)
            _close(out, want)
            assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
            assert float(counters["pairs_here"]) == float(counters["pairs_total"]) == B * T * 2


def test_whole_pass_and_its_gradient_equal_the_reference(uncut):
    s, params = uncut
    cfg = config()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, 64)
    actions = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, 64)

    def program(p):
        logp, entropy, values, _ = lm.evaluate(p, tokens, actions, cfg)
        return jnp.sum(logp) + 0.3 * jnp.sum(entropy) + 0.7 * jnp.sum(values)

    def reference(p):
        final, _ = ref.forward(p, tokens, s)
        logp, entropy, values = ref.heads(p, final, actions, s)
        return jnp.sum(logp) + 0.3 * jnp.sum(entropy) + 0.7 * jnp.sum(values)

    with jax.default_matmul_precision("highest"):
        final, aux = lm.forward(params, tokens, cfg)
        want, choices = ref.forward(params, tokens, s)
        _close(final, want)
        assert np.array_equal(np.asarray(aux["choices"]), np.asarray(choices))
        got, want = jax.grad(program)(params), jax.grad(reference)(params)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * scale + 1e-7, jax.tree_util.keystr(path)
    assert not np.any(np.asarray(got["layers"]["layer_2"]["moe"]["bias"]))  # the bias selects, and learns nothing


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(uncut):
    """Experts 0-1, 2-3, 4-5, 6-7 of 8 (the router whole, two experts a token): what the four
    chips of the deployment compute, each its own experts' part, sums to the uncut reference's layer."""
    s, params = uncut
    p = params["layers"]["layer_2"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (B * T, 32))
    with jax.default_matmul_precision("highest"):
        whole, chosen = ref.moe_ffn(p, x, s, None)
        parts, pairs = [], 0.0
        for lo in range(0, 8, 2):
            share = {**p, **{k: p[k][lo : lo + 2] for k in ("w1", "w3", "w2")}}
            out, share_chosen, counters = lm.moe_ffn(share, x, config(experts_held=2, expert_lo=lo))
            assert np.array_equal(np.asarray(share_chosen), np.asarray(chosen))  # every chip routes alike
            parts.append(out)
            pairs += float(counters["pairs_here"])
            # and the reference given the same share computes the same part
            _close(out, ref.moe_ffn(share, x, {**s, "experts_held": 2, "expert_lo": lo}, None)[0])
    assert pairs == B * T * 2  # every pair is computed on exactly one chip
    _close(sum(parts), whole)
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3  # a share alone is not the layer


def test_grouped_dot_with_rows_that_no_group_covers():
    """Groups that leave the last rows uncovered (the pairs of absent experts): the covered rows are
    each group's product, forwards and backwards, and the uncovered rows take no part going backwards."""
    sizes = np.array([5, 0, 17, 9])
    used, m = int(sizes.sum()), 64
    xs = jax.random.normal(jax.random.PRNGKey(0), (m, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 48)) / 6
    ct = jnp.concatenate([jax.random.normal(jax.random.PRNGKey(2), (used, 48)), jnp.full((m - used, 48), jnp.nan)])
    starts = np.concatenate([[0], np.cumsum(sizes)])

    def dense(xs, w):
        return jnp.concatenate([xs[a:b] @ w[g] for g, (a, b) in enumerate(zip(starts[:-1], starts[1:]))])

    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(lambda xs, w: lm.grouped_dot(xs, w, jnp.asarray(sizes, jnp.int32)), xs, w)
        d_xs, d_w = vjp(ct)  # a not-a-number cotangent on the uncovered rows must not leak
        want, want_vjp = jax.vjp(dense, xs, w)
        want_d_xs, want_d_w = want_vjp(ct[:used])
    _close(out[:used], want)
    _close(d_xs[:used], want_d_xs[:used])
    assert not np.any(np.asarray(d_xs[used:]))
    _close(d_w, want_d_w)


@pytest.mark.parametrize("rows,sizes", [(8, [2, 0, 3, 1]), (64, [5, 0, 17, 9]), (600, [100, 200, 50, 150])])
def test_the_tpu_branch_of_the_grouped_products_in_interpret_mode(monkeypatch, rows, sizes):
    """The branch a TPU takes (the stock Pallas grouped matmul: its tiles, the rows padded to whole
    tiles, a decode step's eight rows among them, the two transposes) run by the Pallas interpreter on
    the CPU gives what ``ragged_dot`` gives, forwards and backwards."""
    import functools
    import importlib

    megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
    xs = jax.random.normal(jax.random.PRNGKey(0), (rows, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 48)) / 6
    ct = jax.random.normal(jax.random.PRNGKey(2), (rows, 48))
    group_sizes, used = jnp.asarray(sizes, jnp.int32), sum(sizes)
    want, want_vjp = jax.vjp(lambda a, b: lm.grouped_dot(a, b, group_sizes), xs, w)
    want_d = want_vjp(ct)
    monkeypatch.setattr(lm, "on_tpu", lambda: True)
    monkeypatch.setattr(megablox, "gmm", functools.partial(megablox.gmm, interpret=True))
    monkeypatch.setattr(megablox, "tgmm", functools.partial(megablox.tgmm, interpret=True))
    got, got_vjp = jax.vjp(lambda a, b: lm.grouped_dot(a, b, group_sizes), xs, w)
    got_d = got_vjp(ct)
    _close(got[:used], want[:used], 1e-4)
    _close(got_d[0], want_d[0], 1e-4)
    _close(got_d[1], want_d[1], 1e-4)


def test_every_token_routed_to_one_expert_loses_none(uncut):
    """No capacity, no dropped token: with a router that sends every token to the same two
    experts, both held here, every pair is computed and the result is the reference's."""
    s, params = uncut
    p = dict(params["layers"]["layer_2"]["moe"])
    p["router"] = jnp.zeros_like(p["router"])
    p["bias"] = jnp.zeros_like(p["bias"]).at[jnp.array([1, 5])].set(1.0)  # equal scores: the bias decides
    x = jax.random.normal(jax.random.PRNGKey(5), (B * T, 32))
    with jax.default_matmul_precision("highest"):
        out, chosen, counters = lm.moe_ffn(p, x, config())
        want, _ = ref.moe_ffn(p, x, s, None)
    assert set(np.unique(np.asarray(chosen))) == {1, 5}
    assert float(counters["pairs_here"]) == B * T * 2 and float(counters["rows_per_expert_min"]) == 0.0
    assert float(counters["load_max_over_mean"]) == pytest.approx(4.0)  # two of eight experts hold every row
    _close(out, want)


def test_decoding_through_the_state_equals_the_full_pass_across_a_reset(uncut):
    """One token a step through conv tails and the key-value cache gives the logits (not
    only the tokens) of the full pass; after a reset of one sequence it gives those of a
    fresh sequence, while the other sequence carries on."""
    _, params = uncut
    cfg = config(experts_held=8)
    first = jax.random.randint(jax.random.PRNGKey(6), (B, T), 0, 64)
    second = jax.random.randint(jax.random.PRNGKey(7), (B, T), 0, 64)
    cut = 20  # sequence 0 ends after 20 steps and starts `second`; sequence 1 runs `first` to its end
    step = jax.jit(lambda p, t, st: lm.decode_step(p, t, st, cfg))
    with jax.default_matmul_precision("highest"):
        want_first, values_first = lm.logits_and_values(params, first, cfg)
        want_second, _ = lm.logits_and_values(params, second, cfg)
        state = lm.init_state(cfg, B)
        for t in range(T):
            if t == cut:
                state = lm.reset_state(state, jnp.array([0.0, 1.0]))
            tokens = jnp.stack([first[0, t] if t < cut else second[0, t - cut], first[1, t]])
            logits, values, state = step(params, tokens, state)
            _close(logits[1], want_first[1, t], 1e-4)
            _close(values[1], values_first[1, t], 1e-4)
            _close(logits[0], want_first[0, t] if t < cut else want_second[0, t - cut], 1e-4)
    assert np.array_equal(np.asarray(state["pos"]), [T - cut, T])


def test_bfloat16_working_copy_keeps_router_and_norms_in_float32(uncut):
    _, params = uncut
    copy = lm.working_copy(params, jnp.bfloat16)
    moe = copy["layers"]["layer_2"]["moe"]
    assert moe["router"].dtype == moe["bias"].dtype == copy["final_norm"].dtype == jnp.float32
    assert moe["w1"].dtype == copy["embed"].dtype == copy["layers"]["layer_1"]["attn"]["q"].dtype == jnp.bfloat16
    cfg = config()
    tokens = jax.random.randint(jax.random.PRNGKey(8), (B, T), 0, 64)
    final, _ = lm.forward(params, tokens, cfg, jnp.bfloat16)
    assert final.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(final.astype(jnp.float32))))
