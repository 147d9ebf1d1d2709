"""The language-model policy's blocks (``sheeprl_tpu/models/lm.py``) against the plain
reference (``benchmarks/chip/reference/lfm2_ppo.py``, float32, nothing imported from the
program), at small sizes on the CPU: each part, the whole pass, the chip's share of an
expert layer, and acting through the carried state."""

import functools
import importlib
import os
import re
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
