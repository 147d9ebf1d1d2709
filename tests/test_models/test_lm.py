"""The language-model policy's blocks (``sheeprl_tpu/models/lm.py``) against the plain
references (``benchmarks/chip/reference/lfm2_ppo.py``, ``trinity_ppo.py`` and ``smallthinker_ppo.py``,
float32, nothing imported from the program), at small sizes on the CPU: each part, the whole pass,
the chip's share of an expert layer, and acting through the carried state. Where a test is the same
for the families it is one test with a case a family."""

import dataclasses
import functools
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

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


# ------------------------------------------------------------------ the second family, at small sizes
# Trinity-Mini's pattern: three sliding layers then a full one; two leading dense layers. Published layers 0
# and 4-7 are run, as in the benchmark's cut. The window is 8 and a sequence 32 positions: four windows.
ref2 = common.load_module("reference", "trinity_ppo")
LAYER_TYPES2 = (["sliding_attention"] * 3 + ["full_attention"]) * 2
WINDOW = 8
SIZES2 = dict(
    hidden_size=32, layers=[0, 4, 5, 6, 7], layer_types=LAYER_TYPES2, num_dense_layers=2, intermediate_size=48,
    moe_intermediate_size=24, num_experts=16, num_experts_per_tok=4, experts_held=16, expert_lo=0, num_shared_experts=1,
    vocab=64, num_attention_heads=4, num_key_value_heads=1, head_dim=8, rope_theta=1e4, rope_layer_types=["sliding_attention"],
    sliding_window=WINDOW, norm_eps=1e-5, norm_topk_prob=True, routed_scaling_factor=2.826, route_eps=1e-20, query_block=8,
)


def config2(**kw):
    base = dict(
        hidden_size=32, layers=(0, 4, 5, 6, 7), layer_types=tuple(LAYER_TYPES2), num_dense_layers=2, intermediate_size=48,
        moe_intermediate_size=24, num_experts=16, num_experts_per_tok=4, experts_held=16, vocab_held=64,
        num_attention_heads=4, num_key_value_heads=1, head_dim=8, max_positions=T, query_block=8, head_chunk=8,
        rope_theta=1e4, sliding_window=WINDOW, rope_layer_types=("sliding_attention",), attn_output_gate=True, post_norms=True,
        num_shared_experts=1, tie_embedding=False, mup_enabled=True, route_eps=1e-20, routed_scaling_factor=2.826,
    )
    base.update(kw)
    return lm.LMConfig(**base)


@pytest.fixture(scope="module")
def uncut2():
    s = ref2.sizes_from(SIZES2)
    return s, ref2.make_params(ref2.param_spec(s), 11)


# ------------------------------------------------------------------ the third family, at small sizes
# SmallThinker's pattern: a full layer without rotary, then three sliding ones with it, every layer with experts;
# published layers 0-3 are run, as in the benchmark's cut. Seven query heads on one key-value head (the published
# 28 on 4: a group of 7), 64 experts at 6 a token, a router that reads the block's input, ReLU-gated experts.
ref3 = common.load_module("reference", "smallthinker_ppo")
LAYER_TYPES3 = (["full_attention"] + ["sliding_attention"] * 3) * 2
SIZES3 = dict(
    hidden_size=32, layers=[0, 1, 2, 3], layer_types=LAYER_TYPES3, num_dense_layers=0, moe_intermediate_size=24,
    num_experts=64, num_experts_per_tok=6, experts_held=64, expert_lo=0, vocab=64, num_attention_heads=7,
    num_key_value_heads=1, head_dim=8, rope_theta=1.5e6, rope_layer_types=["sliding_attention"], sliding_window=WINDOW,
    norm_eps=1e-6, norm_topk_prob=True, query_block=8,
)


def config3(**kw):
    base = dict(
        hidden_size=32, layers=(0, 1, 2, 3), layer_types=tuple(LAYER_TYPES3), num_dense_layers=0, intermediate_size=0,
        moe_intermediate_size=24, num_experts=64, num_experts_per_tok=6, experts_held=64, vocab_held=64,
        num_attention_heads=7, num_key_value_heads=1, head_dim=8, max_positions=T, query_block=8, head_chunk=8,
        rope_theta=1.5e6, norm_eps=1e-6, sliding_window=WINDOW, rope_layer_types=("sliding_attention",), tie_embedding=False,
        qk_norm=False, hidden_act="relu", router_apply_softmax=True, early_router=True,
    )
    base.update(kw)
    return lm.LMConfig(**base)


@pytest.fixture(scope="module")
def uncut3():
    s = ref3.sizes_from(SIZES3)
    return s, ref3.make_params(ref3.param_spec(s), 11)


FAMILIES = {"lfm2": (ref, config, "uncut"), "trinity": (ref2, config2, "uncut2"), "smallthinker": (ref3, config3, "uncut3")}


@pytest.fixture(params=list(FAMILIES))
def family(request):
    """(reference, config maker, (sizes, uncut weights)) of one family."""
    reference, make, fixture = FAMILIES[request.param]
    return reference, make, request.getfixturevalue(fixture)


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


def test_whole_pass_and_its_gradient_equal_the_reference(family):
    ref, config, (s, params) = family
    cfg = config()
    assert {k: v[0] for k, v in lm.param_shapes(cfg).items()} == {k: v[0] for k, v in ref.param_spec(s).items()}
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
    assert not np.any(np.asarray(got["layers"]["layer_2"]["moe"].get("bias", 0.0)))  # the bias selects, and learns nothing
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves({**got, "layers": None}))  # no top leaf is left out


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


def _take_the_tpu_branch_interpreted(monkeypatch):
    """From here the program takes the branch a TPU takes, its Pallas kernels run by the interpreter."""
    megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")
    monkeypatch.setattr(lm, "on_tpu", lambda: True)
    monkeypatch.setattr(megablox, "gmm", functools.partial(megablox.gmm, interpret=True))
    monkeypatch.setattr(megablox, "tgmm", functools.partial(megablox.tgmm, interpret=True))


@pytest.mark.parametrize("rows,sizes", [(8, [2, 0, 3, 1]), (64, [5, 0, 17, 9]), (600, [100, 200, 50, 150])])
def test_the_tpu_branch_of_the_grouped_products_in_interpret_mode(monkeypatch, rows, sizes):
    """The branch a TPU takes (the stock Pallas grouped matmul: its tiles, the rows padded to whole
    tiles, a decode step's eight rows among them, the two transposes) run by the Pallas interpreter on
    the CPU gives what ``ragged_dot`` gives, forwards and backwards."""
    xs = jax.random.normal(jax.random.PRNGKey(0), (rows, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 48)) / 6
    ct = jax.random.normal(jax.random.PRNGKey(2), (rows, 48))
    group_sizes, used = jnp.asarray(sizes, jnp.int32), sum(sizes)
    want, want_vjp = jax.vjp(lambda a, b: lm.grouped_dot(a, b, group_sizes), xs, w)
    want_d = want_vjp(ct)
    _take_the_tpu_branch_interpreted(monkeypatch)
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


def test_decoding_through_the_state_equals_the_full_pass_across_a_reset(family):
    """One token a step through conv tails and the key-value cache gives the logits (not
    only the tokens) of the full pass; after a reset of one sequence it gives those of a
    fresh sequence, while the other sequence carries on. The second family's sliding layers
    carry a ring of the window's 8 positions, which a sequence of 32 goes round four times,
    and the reset comes in the middle of a round, with the last episode's rows still in the ring."""
    _, config, (_, params) = family
    cfg = config()
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
    rows = {leaf.shape[1] for path, leaf in jax.tree_util.tree_flatten_with_path(state["layers"])[0] if path[-1].key == "k"}
    assert rows == ({T} if cfg.sliding_window is None else {WINDOW, T})  # two sizes of cache in one carried state


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


# ------------------------------------------------- the compact row buffer of the expert layer


@jax.custom_vjp
def _parent_rows_to_pairs(x, order, inverse):
    return x[order // (order.shape[0] // x.shape[0])]


_parent_rows_to_pairs.defvjp(
    lambda x, order, inverse: (_parent_rows_to_pairs(x, order, inverse), (inverse, x.shape[0])),
    lambda res, g: (jnp.sum(g[res[0]].reshape(res[1], -1, g.shape[-1]), axis=1), None, None),
)


@jax.custom_vjp
def _parent_pairs_to_slots(ys, order, inverse):
    return ys[inverse]


_parent_pairs_to_slots.defvjp(lambda ys, order, inverse: (ys[inverse], order), lambda order, g: (g[order], None, None))


def _parent_moe_ffn(p, x, cfg):
    """``lm.moe_ffn`` as it stood before the compact buffer (commit 5e33a18), kept word for word
    but for its counters: every array between the routing and the sum has a row for every pair."""
    lo = cfg.expert_lo
    held_n = p["w1"].shape[0]
    n_rows, k = x.shape[0], cfg.num_experts_per_tok
    chosen, w = lm.route(p, x, cfg)
    local = chosen - lo
    held = (local >= 0) & (local < held_n)
    sort_key = jnp.where(held, local, held_n).reshape(-1)
    order = jnp.argsort(sort_key, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0], dtype=order.dtype))
    group_sizes = jnp.sum(sort_key[:, None] == jnp.arange(held_n)[None, :], axis=0, dtype=jnp.int32)
    xs = _parent_rows_to_pairs(x, order, inverse)
    hidden = jax.nn.silu(lm.grouped_dot(xs, p["w1"], group_sizes)) * lm.grouped_dot(xs, p["w3"], group_sizes)
    ys = lm.grouped_dot(hidden, p["w2"], group_sizes)
    back = _parent_pairs_to_slots(ys, order, inverse).reshape(n_rows, k, -1)
    weight = jnp.where(held, w, 0.0).astype(back.dtype)
    return jnp.sum(jnp.where(held[..., None], back, 0) * weight[..., None], axis=1)


QUARTER = dict(experts_held=2, expert_lo=2)  # experts 2 and 3 of 8 are held: a quarter, as in the benchmark's cell
TOKENS = 512  # 1,024 pairs; the compact buffer is one row tile of the grouped matmul, 512 rows
# held pairs -> whether the layer takes the compact width
WIDTH_CASES = {"well_under": (100, 1.0), "exactly_full": (512, 1.0), "one_over": (513, 0.0), "all_pairs": (1024, 0.0)}


def _quarter_layer(held_pairs):
    """An expert layer whose router reads each token's two experts off the token's first eight
    features, and rows that send exactly ``held_pairs`` (token, slot) pairs to experts 2 and 3."""
    p = {
        "router": jnp.zeros((32, 8)).at[:8].set(4.0 * jnp.eye(8)),
        "bias": jax.random.uniform(jax.random.PRNGKey(20), (8,), jnp.float32, -0.02, 0.02),
        **{
            name: jax.random.normal(jax.random.PRNGKey(21 + i), shape) / 6
            for i, (name, shape) in enumerate({"w1": (2, 32, 24), "w3": (2, 32, 24), "w2": (2, 24, 32)}.items())
        },
    }
    both = max(held_pairs - TOKENS, 0)  # tokens with both of their experts held
    one = held_pairs - 2 * both  # tokens with one
    first = np.where(np.arange(TOKENS) < both + one, 2 + np.arange(TOKENS) % 2, 4)
    second = np.where(np.arange(TOKENS) < both, 5 - first, 5 + np.arange(TOKENS) % 3)
    rng = np.random.default_rng(held_pairs)
    spread = rng.permutation(TOKENS)  # held pairs all over the rows, not in front
    x = rng.normal(size=(TOKENS, 32)).astype(np.float32)
    x[:, :8] = 0.0
    x[spread, first] = 3.0
    x[spread, second] = 2.0
    return p, jnp.asarray(x)


def _out_and_grads(layer, p, x, cfg, ct):
    def loss(p, x):
        return jnp.sum(layer(p, x, cfg) * ct)

    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, x: layer(p, x, cfg))(p, x)
        d_p, d_x = jax.jit(jax.grad(loss, argnums=(0, 1)))(p, x)
    return {"out": out, "rows": d_x, **{k: d_p[k] for k in ("w1", "w3", "w2", "router")}}


def test_the_compact_width_follows_the_shapes():
    assert lm.compact_rows(65536, 8, 32) == 24576  # the benchmark's cell: 16,384 tokens, 8 of 32 experts held
    assert lm.compact_rows(8, 8, 32) == 8  # a decode step of two tokens: one tile would hold all pairs
    assert lm.compact_rows(1024, 8, 8) == 1024  # every expert held
    assert lm.compact_rows(1024, 2, 8) == 512 and lm.compact_rows(3000, 2, 8) == 1536  # whole row tiles


@pytest.mark.parametrize("case", list(WIDTH_CASES))
def test_a_quarter_of_the_experts_through_the_compact_buffer_equals_the_full_width(case):
    """With 2 of 8 experts held the buffer has 512 of 1,024 rows. Outputs and gradients (rows,
    three kernels, router) are the parent's full-width formulation's, whether the held pairs fit
    (the compact width) or not (the fallback, which is that formulation: to the last bit); the
    case of every pair held is ``test_every_token_routed_to_one_expert_loses_none``'s, and loses none."""
    held_pairs, compact = WIDTH_CASES[case]
    p, x = _quarter_layer(held_pairs)
    cfg = config(**QUARTER)
    assert lm.compact_rows(2 * TOKENS, 2, 8) == 512
    with jax.default_matmul_precision("highest"):
        _, chosen, counters = lm.moe_ffn(p, x, cfg)
    assert float(counters["pairs_here"]) == held_pairs and float(counters["compact"]) == compact
    assert int(jnp.sum((chosen == 2) | (chosen == 3))) == held_pairs
    ct = jax.random.normal(jax.random.PRNGKey(30), x.shape)
    layer = lambda p, x, cfg: lm.moe_ffn(p, x, cfg)[0]  # noqa: E731
    got, want = _out_and_grads(layer, p, x, cfg, ct), _out_and_grads(_parent_moe_ffn, p, x, cfg, ct)
    for name in want:
        assert float(jnp.max(jnp.abs(want[name]))) > 1e-3
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(want[name]), rtol=1e-5, atol=1e-6, err_msg=name)
    # and to the last bit, op by op (compiled whole, XLA:CPU fuses the two programs' sums over a token's slots apart)
    with jax.disable_jit():
        got, want = _out_and_grads(layer, p, x, cfg, ct), _out_and_grads(_parent_moe_ffn, p, x, cfg, ct)
    for name in want:
        assert np.array_equal(np.asarray(got[name]), np.asarray(want[name])), name
    if case == "all_pairs":  # nothing lost: the uncut reference given the same share computes the same
        s = {**ref.sizes_from(SIZES), "experts_held": 2, "expert_lo": 2}
        with jax.default_matmul_precision("highest"):
            _close(got["out"], ref.moe_ffn(p, x, s, None)[0])


@pytest.mark.parametrize("case", ["well_under", "one_over"])
def test_the_tpu_branch_of_the_expert_layer_in_interpret_mode_through_both_widths(monkeypatch, case):
    """The Pallas grouped matmul on a buffer of 512 rows (the held pairs fit) and of 1,024 (they do
    not), forwards and backwards, gives what the CPU's branch gives."""
    p, x = _quarter_layer(WIDTH_CASES[case][0])
    cfg = config(**QUARTER)
    ct = jax.random.normal(jax.random.PRNGKey(31), x.shape)
    layer = lambda p, x, cfg: lm.moe_ffn(p, x, cfg)[0]  # noqa: E731
    want = _out_and_grads(layer, p, x, cfg, ct)
    _take_the_tpu_branch_interpreted(monkeypatch)
    got = _out_and_grads(layer, p, x, cfg, ct)
    for name in want:
        _close(got[name], want[name], 1e-4)


@pytest.mark.parametrize("experts_held", [8, 2])
def test_the_token_policy_s_metrics_carry_the_compact_share(experts_held):
    from sheeprl_tpu.algos.ppo_recurrent.token_agent import TokenPolicy

    cfg = config(experts_held=experts_held)
    policy = TokenPolicy(cfg, jnp.float32)
    params = lm.init_params(cfg, jax.random.PRNGKey(9))
    ids = jax.random.randint(jax.random.PRNGKey(10), (T, B, 1), 0, 64).astype(jnp.float32)
    *_, metrics = policy.evaluate(params, {"actions": ids}, {"tokens": ids})
    assert float(metrics["Moe/compact_share"]) == 1.0  # four expert layers, each within its buffer
    assert float(metrics["Moe/pairs_here"]) <= float(metrics["Moe/pairs_total"]) == 4 * B * T * 2


# published index of a layer of each kind of block (two leading dense layers, attention at 2 and 6)
BLOCKS = {"conv_dense": 0, "conv_experts": 3, "attention_experts": 2}
_REMADE_PRODUCT = re.compile(r"rematted_computation/lm\.(?:conv|dense_ffn|attn)/dot_general")


@pytest.mark.parametrize("kind", list(BLOCKS))
def test_a_block_keeps_its_products_for_its_backward_pass(kind, monkeypatch, capsys):
    """A block's matmul products are kept for its backward pass and no number moves: the loss and the
    gradient of ``lm.evaluate`` with respect to every parameter equal, to the last bit, those of the
    parent's formulation (a plain ``jax.checkpoint`` with no policy, which keeps the block's input only);
    the lowered gradient makes no product of ``lm.conv``, ``lm.dense_ffn`` or ``lm.attn`` a second time
    where the parent's does; and what the block keeps beside its arguments are those products. (The
    attention here is the CPU's: its scores, ``einsum``s under a ``jax.checkpoint`` of their own, are
    still made again. The router's product is too.)"""
    cfg = config(layers=(BLOCKS[kind],))
    (mixer, ffn), d = cfg.kinds[0], cfg.hidden_size
    params = lm.init_params(cfg, jax.random.PRNGKey(12))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, 64)
    actions = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, 64)

    def loss_and_gradient():  # traced anew for each formulation
        def program(p):
            logp, entropy, values, _ = lm.evaluate(p, tokens, actions, cfg)
            return jnp.sum(logp) + 0.3 * jnp.sum(entropy) + 0.7 * jnp.sum(values)

        fn = jax.jit(jax.value_and_grad(program))
        return fn(params), fn.lower(params).as_text(debug_info=True)

    got, text = loss_and_gradient()
    assert not _REMADE_PRODUCT.search(text)
    assert ("rematted_computation/lm.moe.route/dot_general" in text) == (ffn == "moe")  # the expert layer is as it was

    block = lambda p, x: lm._layer(p, x, cfg, mixer, ffn)[0]  # noqa: E731
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(block, params["layers"]["layer_0"], jnp.zeros((B, T, d)))
    made_here = (line for line in capsys.readouterr().out.splitlines() if " output of " in line)  # the others are arguments
    kept = sorted(re.match(r"f32\[([\d,]+)\]", line).group(1) for line in made_here)
    heads = cfg.head * cfg.num_attention_heads, cfg.head * cfg.num_key_value_heads
    widths = [3 * d, d] if mixer == "conv" else [heads[0], heads[1], heads[1], d]  # in_proj, out_proj | q, k, v, o
    widths += [cfg.intermediate_size] * 2 if ffn == "dense" else []  # w1, w3; an expert layer keeps its inputs only
    assert kept == sorted(f"{B},{T},{w}" for w in widths)

    monkeypatch.setattr(lm, "KEEP_PRODUCTS", None)  # the parent's: `jax.checkpoint(whole)`
    want, parent_text = loss_and_gradient()
    assert _REMADE_PRODUCT.search(parent_text)
    assert float(jnp.abs(want[0])) > 1.0
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w)), jax.tree_util.keystr(path)


# ----------------------------------------------------------------------- the second family's own parts


@pytest.mark.parametrize("part", ["swa", "attn", "moe_with_shared"])
def test_each_new_part_equals_the_reference(uncut2, part):
    """A sliding layer's attention (window, rotary, gate), a full layer's (no rotary, gate), and an expert
    layer with its shared expert under the router's 1e-20 and its scale, each over four windows' length."""
    s, params = uncut2
    cfg = config2()
    n = jax.random.normal(jax.random.PRNGKey(1), (B, T, 32))
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        if part == "swa":
            _close(lm.attn_op(layers["layer_1"]["attn"], n, cfg, "swa")[0], ref2.attn_op(layers["layer_1"]["attn"], n, s, "swa", None))
        elif part == "attn":
            _close(lm.attn_op(layers["layer_4"]["attn"], n, cfg, "attn")[0], ref2.attn_op(layers["layer_4"]["attn"], n, s, "attn", None))
        else:
            # the half-block around the layer without its post-norm: it norms its input, so the reference is handed the normed rows
            got, (chosen, counters) = lm._ffn_half(layers["layer_2"], n, dataclasses.replace(cfg, post_norms=False), "moe")
            normed = ref2.rms_norm(n.reshape(-1, 32), layers["layer_2"]["ffn_norm"], 1e-5)
            want, want_chosen = ref2.moe_ffn(layers["layer_2"]["moe"], normed, s, None)
            _close(got - n, want.reshape(n.shape))
            assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
            assert float(counters["pairs_here"]) == float(counters["pairs_total"]) == B * T * 4


def test_the_sixteen_shares_of_an_expert_layer_and_the_shared_expert_once_add_up_to_the_uncut_layer(uncut2):
    """Experts 0, 1, ..., 15 of 16, one a chip (the router whole, four experts a token): what the sixteen chips of
    the deployment compute, each its own expert's part, and the shared expert counted once, sum to the uncut
    reference's layer."""
    s, params = uncut2
    p = params["layers"]["layer_2"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (B * T, 32))
    with jax.default_matmul_precision("highest"):
        whole, chosen = ref2.moe_ffn(p, x, s, None)
        shared = lm.gated_mlp(p["shared"], x, "lm.moe.shared")
        parts, pairs = [], 0.0
        for lo in range(16):
            share = {**p, **{k: p[k][lo : lo + 1] for k in ("w1", "w3", "w2")}}
            out, share_chosen, counters = lm.moe_ffn(share, x, config2(experts_held=1, expert_lo=lo))
            assert np.array_equal(np.asarray(share_chosen), np.asarray(chosen))  # every chip routes alike
            parts.append(out)
            pairs += float(counters["pairs_here"])
            # and the reference given the same share computes the same part beside the shared expert
            _close(out + shared, ref2.moe_ffn(share, x, {**s, "experts_held": 1, "expert_lo": lo}, None)[0])
    assert pairs == B * T * 4  # every pair is computed on exactly one chip
    _close(sum(parts) + shared, whole)
    assert float(jnp.max(jnp.abs(sum(parts) + 16 * shared - whole))) > 1e-2  # counted on every chip it would be wrong
    assert lm.compact_rows(B * T * 4, 1, 16) == B * T * 4  # at these sizes one row tile holds all pairs
    assert lm.compact_rows(2 * 8192 * 8, 8, 128) == 12288  # the benchmark's cell: 131,072 pairs, 8 of 128 experts held


PLANTED = {
    "window_ignored": dict(sliding_window=T),
    "gate_left_out": dict(attn_output_gate=False),
    "post_norms_left_out": dict(post_norms=False),
    "rotary_in_every_layer": dict(rope_layer_types=("sliding_attention", "full_attention")),
    "rotary_in_the_full_layers_only": dict(rope_layer_types=("full_attention",)),
    "embedding_not_scaled": dict(mup_enabled=False),
    "head_tied": dict(tie_embedding=True),
    "router_s_older_epsilon_and_scale": dict(route_eps=1e-6, routed_scaling_factor=1.0),
}


def _the_tpu_branch_interpreted_equals_the_plain_form(monkeypatch, fn, args, ct):
    """``fn(*args)`` and its gradients for the cotangent ``ct``, first through the plain form and then through the
    branch a TPU takes with the splash kernels run by the Pallas interpreter: equal, and no gradient leaf is zero."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as kernel

    want, want_vjp = jax.vjp(fn, *args)
    monkeypatch.setattr(lm, "on_tpu", lambda: True)
    monkeypatch.setattr(kernel, "make_splash_mha", functools.partial(kernel.make_splash_mha, interpret=True))
    got, got_vjp = jax.vjp(fn, *args)
    _close(got, want, 2e-4)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got_vjp(ct))[0], jax.tree_util.tree_leaves(want_vjp(ct))):
        assert float(jnp.max(jnp.abs(w))) > 0, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-4 * (float(jnp.max(jnp.abs(w))) + 1.0), jax.tree_util.keystr(path)


@pytest.mark.parametrize("mixer", ["swa", "attn"])
def test_the_tpu_branch_of_the_attention_call_in_interpret_mode(monkeypatch, mixer):
    """The branch a TPU takes through a model with a window (stock splash attention: 8 query heads on 1 key-value
    head in place, blocks of 128, a local mask of 256 over 512 positions or the causal one) run by the Pallas
    interpreter on the CPU gives what the blocked plain form gives, forwards and backwards."""
    cfg = config2(num_attention_heads=8, head_dim=128, hidden_size=64, sliding_window=256, max_positions=512, query_block=64)
    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    shapes = {"q": (64, 1024), "k": (64, 128), "v": (64, 128), "gate": (64, 1024), "o": (1024, 64)}
    p = {name: jax.random.normal(k, shape) / 8 for k, (name, shape) in zip(ks, shapes.items())}
    p.update(q_norm=jnp.ones((128,)), k_norm=jnp.ones((128,)))
    n = jax.random.normal(ks[7], (2, 512, 64))
    ct = jax.random.normal(ks[8], (2, 512, 64))

    _the_tpu_branch_interpreted_equals_the_plain_form(monkeypatch, lambda p, n: lm.attn_op(p, n, cfg, mixer)[0], (p, n), ct)


# an attention block of each kind the benchmark's cells run, at sizes the splash kernels take: (config maker, mixer, its own sizes)
BLOCKS_AROUND_THE_KERNEL = {
    "first_family_attn": (config, "attn", dict(layer_types=("full_attention",) * 8, num_key_value_heads=2, head_dim=64)),
    "second_family_swa": (config2, "swa", dict(num_key_value_heads=1, head_dim=128, sliding_window=256)),
    "second_family_attn": (config2, "attn", dict(num_key_value_heads=1, head_dim=128, sliding_window=256)),
    # no per-head norm: scale and rotary, or the scale alone, in the same one pass; seven query heads on one key-value head
    "third_family_swa": (config3, "swa", dict(num_attention_heads=7, num_dense_layers=1, intermediate_size=48, head_dim=128, sliding_window=256)),
    "third_family_attn": (config3, "attn", dict(num_attention_heads=7, num_dense_layers=1, intermediate_size=48, head_dim=128, sliding_window=256)),
}


@pytest.mark.parametrize("case", list(BLOCKS_AROUND_THE_KERNEL))
def test_the_tpu_branch_of_an_attention_block_in_interpret_mode(monkeypatch, case):
    """``_layer`` of an attention block with a dense FFN through the branch a TPU takes (its two halves around stock
    splash attention: the products made heads-first, norm, rotary and scale in one rounding, the gate and the
    output projection from the kernel's layout; the kernels run by the Pallas interpreter on the CPU) gives what
    the plain form gives in ``[B, T, H, hd]``: the block's output and the gradient of every leaf and of the input."""
    make, mixer, sizes = BLOCKS_AROUND_THE_KERNEL[case]
    cfg = make(**{**dict(layers=(0,), hidden_size=64, num_attention_heads=8, max_positions=512, query_block=64), **sizes})
    assert cfg.kinds[0][1] == "dense"  # layer 0's weights; the second family's full block runs on a sliding layer's, alike in shape
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    p = lm.init_params(cfg, keys[0])["layers"]["layer_0"]
    scales = {"q_norm", "k_norm", "op_norm", "ffn_norm", "op_post_norm", "ffn_post_norm"}  # they start at 1: moved, so that their gradients are told apart
    p = jax.tree_util.tree_map_with_path(lambda path, w: w * jnp.linspace(0.5, 1.5, w.shape[-1]) if path[-1].key in scales else w, p)
    x = jax.random.normal(keys[1], (2, 512, 64))
    ct = jax.random.normal(keys[2], (2, 512, 64))

    _the_tpu_branch_interpreted_equals_the_plain_form(monkeypatch, lambda p, x: lm._layer(p, x, cfg, mixer, "dense")[0], (p, x), ct)


def test_the_cache_holds_the_keys_of_the_whole_pass_to_the_bit_in_bfloat16(family):
    """In ``bf16-mixed`` the rollout's keys are the learner's: a decode step writes into its cache, position by
    position, the very bits that the whole-sequence pass makes of the same normed rows (one function norms, rotates
    and rounds once, whatever the layout: a step at a time, ``[B, T, G, hd]``, or the kernel's ``[B, G, T, hd]``).
    The second family's sliding layer goes round its ring of 8 rows four times; the last round is compared."""
    _, config, (_, params) = family
    cfg = config()
    n, mixer = next((i, m) for i, (m, _) in enumerate(cfg.kinds) if m != "conv")
    p = lm.working_copy(params, jnp.bfloat16)["layers"][f"layer_{n}"]["attn"]
    normed = jax.random.normal(jax.random.PRNGKey(9), (B, T, 32)).astype(jnp.bfloat16)
    _, keys, values = lm.attn_op(p, normed, cfg, mixer)
    rotary, positions = cfg.rotary(mixer), jnp.arange(T)[None, :]
    for plain, first in zip(lm._qkv(p, normed, cfg, positions, rotary), lm._qkv(p, normed, cfg, positions, rotary, heads_first=True)):
        assert plain.dtype == jnp.bfloat16 and np.array_equal(np.asarray(plain, np.float32), np.asarray(jnp.swapaxes(first, 1, 2), np.float32))
    state = lm.init_state(cfg, B, jnp.bfloat16)["layers"][f"layer_{n}"]
    cache_k, cache_v = state["k"], state["v"]
    rows = cache_k.shape[1]
    for t in range(T):
        _, cache_k, cache_v = lm.attn_decode(p, normed[:, t : t + 1], cfg, cache_k, cache_v, jnp.full((B,), t), mixer)
    kept = np.arange(T - rows, T)
    assert rows == (WINDOW if mixer == "swa" else T) and float(jnp.max(jnp.abs(keys.astype(jnp.float32)))) > 0.1
    assert np.array_equal(np.asarray(cache_k, np.float32)[:, kept % rows], np.asarray(keys, np.float32)[:, kept])
    assert np.array_equal(np.asarray(cache_v, np.float32)[:, kept % rows], np.asarray(values, np.float32)[:, kept])


def test_the_second_family_s_working_copy_and_config_group():
    """The four norms stay float32 in the bfloat16 working copy, and the shipped config group composes to the
    published model: 32 layers (24 sliding, 8 full), 128 experts held, 200,192 rows, rotary in the sliding layers."""
    from sheeprl_tpu.config import compose

    cfg = config2()
    fresh = lm.init_params(cfg, jax.random.PRNGKey(0))
    copy = lm.working_copy(fresh, jnp.bfloat16)
    # the program's own fresh weights start every norm at 1, as the published modelling code does; the benchmark's seeded
    # weights start the two post-norms at 1 / sqrt(2 x 8 published layers) here (`assumed.weights` of its configuration)
    made = ref2.make_params(ref2.param_spec(ref2.sizes_from(SIZES2)), 0)
    for name, seeded in (("op_post_norm", 0.25), ("ffn_post_norm", 0.25), ("op_norm", 1.0), ("ffn_norm", 1.0)):
        assert np.all(np.asarray(fresh["layers"]["layer_1"][name]) == 1.0) and np.all(np.asarray(made["layers"]["layer_1"][name]) == seeded)
    layer = copy["layers"]["layer_1"]
    assert layer["op_post_norm"].dtype == layer["ffn_post_norm"].dtype == layer["attn"]["q_norm"].dtype == jnp.float32
    assert layer["attn"]["gate"].dtype == layer["moe"]["shared"]["w1"].dtype == copy["head"].dtype == jnp.bfloat16
    for exp, layers, held, rows in (("ppo_recurrent_trinity_tokens", (0, 4, 5, 6, 7), 8, 25024),):
        published = lm.LMConfig.from_cfg(compose(config_name="config", overrides=[f"exp={exp}"]).algo.lm)
        assert (published.layers, published.experts_held, published.vocab_held) == (layers, held, rows)
        assert published.kinds == (("swa", "dense"),) + (("swa", "moe"),) * 3 + (("attn", "moe"),)
        assert published.rotary("swa") and not published.rotary("attn") and published.window("swa") == 2048
        assert published.embed_scale == pytest.approx(2048**0.5) and not published.tie_embedding and published.route_eps == 1e-20
    with open(os.path.join(ROOT, "sheeprl_tpu", "configs", "algo", "ppo_recurrent_trinity.yaml")) as f:
        uncut = lm.LMConfig.from_cfg({**yaml.safe_load(f)["lm"], "max_positions": 8192})  # the config group alone: the uncut model
    assert len(uncut.layers) == 32 and uncut.experts_held == 128 and uncut.vocab_held == 200192
    assert [m for m, _ in uncut.kinds].count("attn") == 8 and [f for _, f in uncut.kinds].count("dense") == 2
    first = lm.LMConfig.from_cfg(compose(config_name="config", overrides=["exp=ppo_recurrent_lfm2_tokens"]).algo.lm)
    assert first.sliding_window is None and first.rotary("attn") and first.tie_embedding and first.embed_scale == 1.0


# ----------------------------------------------------------------------- the third family's own parts


@pytest.mark.parametrize("part", ["swa", "attn", "moe"])
def test_each_part_of_the_third_family_equals_the_reference(uncut3, part):
    """A sliding layer's attention (window, rotary, no per-head norm, seven query heads on one key-value head), a
    full layer's (neither rotary nor norm: ``k`` is its product as it is), and an expert layer whose router reads
    other rows than its experts, with softmax weights over the six chosen logits and ReLU-gated experts."""
    s, params = uncut3
    cfg = config3()
    n = jax.random.normal(jax.random.PRNGKey(1), (B, T, 32))
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        if part == "swa":
            _close(lm.attn_op(layers["layer_1"]["attn"], n, cfg, "swa")[0], ref3.attn_op(layers["layer_1"]["attn"], n, s, "swa", None))
        elif part == "attn":
            _close(lm.attn_op(layers["layer_0"]["attn"], n, cfg, "attn")[0], ref3.attn_op(layers["layer_0"]["attn"], n, s, "attn", None))
        else:
            x = jax.random.normal(jax.random.PRNGKey(2), (B * T, 32))  # the block's input, which the router reads
            flat = n.reshape(-1, 32)
            out, chosen, counters = lm.moe_ffn(layers["layer_2"]["moe"], flat, cfg, x)
            want, want_chosen = ref3.moe_ffn(layers["layer_2"]["moe"], flat, x, s, None)
            _close(out, want)
            assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
            assert float(counters["pairs_here"]) == float(counters["pairs_total"]) == B * T * 6
            _, w = lm.route(layers["layer_2"]["moe"], x, cfg)
            _close(jnp.sum(w, axis=-1), jnp.ones(B * T))  # a softmax over the chosen six
            all_experts = jax.nn.softmax(jnp.matmul(x, layers["layer_2"]["moe"]["router"], precision=lm.HI), axis=-1)
            renormalised = jnp.take_along_axis(all_experts, chosen, axis=-1)
            _close(w, renormalised / jnp.sum(renormalised, axis=-1, keepdims=True))  # = the softmax over all, renormalised


def test_the_eight_shares_of_eight_experts_each_add_up_to_the_uncut_layer(uncut3):
    """Experts 0-7, 8-15, ..., 56-63 of 64 (the router whole, six experts a token): what the eight chips of the
    deployment compute, each its own experts' part, sums to the uncut reference's layer; there is no shared expert
    to count once."""
    s, params = uncut3
    p = params["layers"]["layer_2"]["moe"]
    m = jax.random.normal(jax.random.PRNGKey(4), (B * T, 32))
    x = jax.random.normal(jax.random.PRNGKey(5), (B * T, 32))
    with jax.default_matmul_precision("highest"):
        whole, chosen = ref3.moe_ffn(p, m, x, s, None)
        parts, pairs = [], 0.0
        for lo in range(0, 64, 8):
            share = {**p, **{k: p[k][lo : lo + 8] for k in ("w1", "w3", "w2")}}
            out, share_chosen, counters = lm.moe_ffn(share, m, config3(experts_held=8, expert_lo=lo), x)
            assert np.array_equal(np.asarray(share_chosen), np.asarray(chosen))  # every chip routes alike
            parts.append(out)
            pairs += float(counters["pairs_here"])
            _close(out, ref3.moe_ffn(share, m, x, {**s, "experts_held": 8, "expert_lo": lo}, None)[0])
    assert pairs == B * T * 6  # every pair is computed on exactly one chip
    _close(sum(parts), whole)
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3  # a share alone is not the layer
    assert lm.compact_rows(16384 * 6, 8, 64) == 18432  # the benchmark's cell: 98,304 pairs, 12,288 expected here


def test_the_routing_s_gradient_reaches_the_block_s_input_and_not_the_state_after_attention(uncut3):
    """The second half of a block of the third family over the state ``h`` after attention and the block's input
    ``x``: the choices follow ``x`` alone, the router's gradient is the reference's, and the same weights under a
    router that stands after attention (the other families' place) route by ``h`` and leave ``x`` without a gradient."""
    s, params = uncut3
    cfg, layer = config3(), params["layers"]["layer_2"]
    h = jax.random.normal(jax.random.PRNGKey(6), (B, T, 32))
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, 32))
    ct = jax.random.normal(jax.random.PRNGKey(8), (B, T, 32))

    def program(layer, h, x, cfg=cfg):
        return jnp.sum(lm._ffn_half(layer, h, cfg, "moe", x)[0] * ct)

    def reference(layer, h, x):
        m = ref3.rms_norm(h, layer["ffn_norm"], 1e-6).reshape(-1, 32)
        return jnp.sum((h + ref3.moe_ffn(layer["moe"], m, x.reshape(-1, 32), s, None)[0].reshape(h.shape)) * ct)

    with jax.default_matmul_precision("highest"):
        got, want = jax.grad(program, argnums=(0, 1, 2))(layer, h, x), jax.grad(reference, argnums=(0, 1, 2))(layer, h, x)
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            _close(g, w, 1e-4)
        assert float(jnp.max(jnp.abs(got[2]))) > 1e-3  # the block's input gets the routing weights' gradient
        chosen = lm._ffn_half(layer, h, cfg, "moe", x)[1][0]
        assert np.array_equal(np.asarray(lm._ffn_half(layer, h + 1.0, cfg, "moe", x)[1][0]), np.asarray(chosen))
        assert not np.array_equal(np.asarray(lm._ffn_half(layer, h, cfg, "moe", x + 1.0)[1][0]), np.asarray(chosen))
        late = config3(early_router=False)
        assert not np.any(np.asarray(jax.grad(program, argnums=2)(layer, h, x, late)))
        assert not np.array_equal(np.asarray(lm._ffn_half(layer, h, late, "moe", x)[1][0]), np.asarray(chosen))


def test_the_grouped_matmul_s_tiles_follow_the_products_shapes():
    """(512, 1024, 1024) at every product of the two accepted families, forwards and both transposes (hidden 2,048
    against experts of 1,792 and of 1,024), and whole tiles at the third's 2,560 and 768."""
    for hidden, width in ((2048, 1792), (2048, 1024)):
        assert lm.gmm_tiles(24576, hidden, width) == lm.gmm_tiles(12288, width, hidden) == (512, 1024, 1024)
    for rows in (18432, 98304, 512):
        rows_tile, contraction, columns = lm.gmm_tiles(rows, 2560, 768)
        assert rows_tile == 512 and 2560 % contraction == 0 and 768 % columns == 0
        assert lm.gmm_tiles(rows, 768, 2560) == (512, columns, contraction)
    assert lm.gmm_tiles(8, 32, 24) == (512, 1024, 1024)  # a test's widths: one tile, as before
    assert lm.ROW_TILE == 512 and lm.compact_rows(3000, 2, 8) == 1536  # the buffer's rows are whole row tiles


PLANTED3 = {
    "router_after_attention": dict(early_router=False),
    "experts_gated_by_silu": dict(hidden_act="silu"),
    "softmax_over_all_experts_not_renormalised": dict(norm_topk_prob=False),
    "window_ignored": dict(sliding_window=T),
    "rotary_in_every_layer": dict(rope_layer_types=("sliding_attention", "full_attention")),
    "rotary_in_the_full_layers_only": dict(rope_layer_types=("full_attention",)),
    "head_tied": dict(tie_embedding=True),
    "five_experts_a_token": dict(num_experts_per_tok=5),
}


@pytest.mark.parametrize("family,fault", [("trinity", f) for f in PLANTED] + [("smallthinker", f) for f in PLANTED3])
def test_a_sequence_of_four_windows_fails_where_a_part_of_a_family_is_left_out(request, family, fault):
    """Each of the second and the third family's own rules planted wrong through its config key (the weights stay; a leaf
    the faulty model does not read is ignored): the logits over four windows' length leave the reference's by far more
    than the tolerance the sound program keeps."""
    reference, make, fixture = FAMILIES[family]
    s, params = request.getfixturevalue(fixture)
    planted = (PLANTED if family == "trinity" else PLANTED3)[fault]
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, 64)
    with jax.default_matmul_precision("highest"):
        final, _ = reference.forward(params, tokens, s)
        want = jnp.matmul(final, params["head"].T)
        sound, _ = lm.logits_and_values(params, tokens, make())
        faulty, _ = lm.logits_and_values(params, tokens, make(**planted))
    _close(sound, want, 1e-4)
    assert float(jnp.max(jnp.abs(faulty - want))) > 100 * 1e-4, fault


def test_the_third_family_s_leaves_and_config_group():
    """No per-head norm and no expert bias among the leaves (the other families keep theirs); the router stays float32
    in the bfloat16 working copy; the shipped config group composes to the published model (52 layers: 13 full without
    rotary, 39 sliding with it; 64 experts held; 151,936 rows) and the experiment to the benchmark's cut."""
    from sheeprl_tpu.config import compose

    names = {path[-1] for path in lm.param_shapes(config3())}
    assert not names & {"q_norm", "k_norm", "bias", "gate", "shared", "op_post_norm"} and {"router", "head", "q", "w2"} <= names
    assert {"q_norm", "k_norm", "bias"} <= {path[-1] for path in lm.param_shapes(config())}
    assert {"q_norm", "k_norm", "bias"} <= {path[-1] for path in lm.param_shapes(config2())}
    copy = lm.working_copy(lm.init_params(config3(), jax.random.PRNGKey(0)), jnp.bfloat16)
    layer = copy["layers"]["layer_1"]
    assert layer["moe"]["router"].dtype == layer["op_norm"].dtype == jnp.float32 and layer["moe"]["w1"].dtype == copy["head"].dtype == jnp.bfloat16
    cut = lm.LMConfig.from_cfg(compose(config_name="config", overrides=["exp=ppo_recurrent_smallthinker_tokens"]).algo.lm)
    assert (cut.layers, cut.experts_held, cut.vocab_held, cut.max_positions) == ((0, 1, 2, 3), 8, 18992, 16384)
    assert cut.kinds == (("attn", "moe"),) + (("swa", "moe"),) * 3
    assert cut.rotary("swa") and not cut.rotary("attn") and cut.window("swa") == 4096 and cut.rope_theta == 1.5e6
    assert cut.early_router and cut.router_apply_softmax and cut.hidden_act == "relu" and not cut.qk_norm and not cut.tie_embedding
    assert (cut.hidden_size, cut.num_attention_heads, cut.num_key_value_heads, cut.head, cut.moe_intermediate_size) == (2560, 28, 4, 128, 768)
    assert (cut.num_experts, cut.num_experts_per_tok, cut.norm_eps, cut.embed_scale) == (64, 6, 1e-6, 1.0)
    with open(os.path.join(ROOT, "sheeprl_tpu", "configs", "algo", "ppo_recurrent_smallthinker.yaml")) as f:
        uncut = lm.LMConfig.from_cfg({**yaml.safe_load(f)["lm"], "max_positions": 16384})  # the config group alone: the uncut model
    assert len(uncut.layers) == 52 and uncut.experts_held == 64 and uncut.vocab_held == 151936
    assert [m for m, _ in uncut.kinds].count("attn") == 13 and all(f == "moe" for _, f in uncut.kinds)
    for exp in ("ppo_recurrent_lfm2_tokens", "ppo_recurrent_trinity_tokens"):  # the accepted families keep the defaults
        other = lm.LMConfig.from_cfg(compose(config_name="config", overrides=[f"exp={exp}"]).algo.lm)
        assert other.qk_norm and other.hidden_act == "silu" and not other.router_apply_softmax and not other.early_router
