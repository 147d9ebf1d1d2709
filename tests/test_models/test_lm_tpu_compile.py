"""The language-model policy's two costly parts compile for a described TPU v5e at the
published widths and the benchmark cell's shapes: the attention layer through the stock
Pallas flash-attention kernel (Mosaic has to take it: heads of 64, blocks of 1,024), and an
expert layer through the stock Pallas grouped matmul at both of its widths (the compact row buffer, and a
row for every (token, slot) pair as the fallback); and a block's gradient makes none of the block's matmul
products a second time. No chip is attached: nothing runs, and nothing here is a device number.

The topology is described inside a fixture, never while a module is imported (only one
process may load the TPU's library, and every xdist worker imports every test file), and
this is the only test file that describes one.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sheeprl_tpu.models import lm

LAYER_TYPES = ("conv", "conv", "full_attention", "conv", "conv", "conv") + ("full_attention", "conv", "conv", "conv") * 3 + (
    "full_attention", "conv", "conv", "full_attention", "conv", "conv")
B, T, D = 2, 8192, 2048


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cut():
    return lm.LMConfig(
        hidden_size=D, layers=(0, 2, 3, 4, 5), layer_types=LAYER_TYPES, num_dense_layers=2, intermediate_size=7168,
        moe_intermediate_size=1792, num_experts=32, num_experts_per_tok=4, experts_held=8, vocab_held=16384,
        num_attention_heads=32, num_key_value_heads=8, head_dim=64, max_positions=T,
    )


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # such an entry cannot be read back without a chip
    try:
        # as the CLI sets it for every run (`float32_matmul_precision: high`): Mosaic has no "high", and the
        # kernels have to be traced under their own default all the same
        with jax.default_matmul_precision("high"):
            return jax.jit(fn).lower(*specs).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def test_attention_layer_through_the_flash_kernel_compiles_for_v5e(one_chip, cut, monkeypatch):
    monkeypatch.setattr(lm, "on_tpu", lambda: True)  # no chip is attached here: the program would take its CPU branch
    p = _block(one_chip, "attn", "moe")["attn"]
    grad = jax.grad(lambda p, n: lm.attn_op(p, n, cut)[0].astype(jnp.float32).sum(), argnums=(0, 1))
    text = _compile(grad, p, _spec((B, T, D), jnp.bfloat16, one_chip)).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3  # the forward, dq and dkv kernels are in the program


def _expert_layer(one_chip):
    """The shapes of one expert layer's share: the router whole, 8 of 32 experts' kernels in bfloat16."""
    bf = jnp.bfloat16
    return {
        "router": _spec((D, 32), jnp.float32, one_chip), "bias": _spec((32,), jnp.float32, one_chip),
        "w1": _spec((8, D, 1792), bf, one_chip), "w3": _spec((8, D, 1792), bf, one_chip), "w2": _spec((8, 1792, D), bf, one_chip),
    }


def _computations(text):
    """name -> body of every computation of a compiled program's text."""
    bodies = {}
    for block in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)", text):
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", block)
        if head:
            bodies[head.group(1)] = block
    return bodies


def test_expert_layer_compiles_for_v5e_and_fits(one_chip, cut, monkeypatch):
    monkeypatch.setattr(lm, "on_tpu", lambda: True)  # the Pallas grouped matmul, as on the chip
    p = _expert_layer(one_chip)
    assert lm.compact_rows(B * T * 4, 8, 32) == 24576
    grad = jax.grad(lambda p, x: lm.moe_ffn(p, x, cut)[0].astype(jnp.float32).sum(), argnums=(0, 1), allow_int=True)
    compiled = _compile(grad, p, _spec((B * T, D), jnp.bfloat16, one_chip))
    text = compiled.as_text()
    # The compiler plans for the wider of the two widths, so the figure does not fall with the compact
    # buffer: 1,519,029,760 B here against the parent's 1,248,751,104 (5e33a18, a row for every pair and no
    # branch; my compiles, PR 30). ISSUE 30 expected a third less; that holds for the compact branch's own
    # arrays (asserted below), not for a program that also holds the fallback.
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    # three products forwards and, backwards, two for each of them: nine grouped-matmul kernels a width
    assert text.count('custom_call_target="tpu_custom_call"') >= 18
    # one branch on the data, in the backward rule (the forward one is dead code where only the gradient is
    # asked for, as it is in a layer that `jax.checkpoint` computes again): nothing is differentiated through it
    branches = re.findall(r" conditional\(.*branch_computations=\{(%[\w.\-]+), (%[\w.\-]+)\}", text)
    assert len(branches) == 1
    bodies = _computations(text)
    compact, full = sorted(branches[0], key=lambda name: "[65536,1792]" in bodies[name])
    assert "[24576,1792]" in bodies[compact] and "[65536,1792]" not in bodies[compact]
    assert "[65536,1792]" in bodies[full] and "[24576,1792]" not in bodies[full]
    for name in (compact, full):
        assert bodies[name].count('custom_call_target="tpu_custom_call"') == 9


def test_a_decode_step_s_expert_layer_compiles_for_v5e(one_chip, cut, monkeypatch):
    """Acting is two tokens a step: eight (token, slot) pairs, padded to one tile of the grouped matmul."""
    monkeypatch.setattr(lm, "on_tpu", lambda: True)
    p = _expert_layer(one_chip)
    text = _compile(lambda p, x: lm.moe_ffn(p, x, cut)[0], p, _spec((2, D), jnp.bfloat16, one_chip)).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert " conditional(" not in text  # eight pairs round up to all of them: one width, the parent's program


def _block(one_chip, mixer, ffn):
    """The shapes of one block's working copy: matmul operands in bfloat16, norm scales in float32."""
    bf, f32 = jnp.bfloat16, jnp.float32
    p = {"op_norm": _spec((D,), f32, one_chip), "ffn_norm": _spec((D,), f32, one_chip)}
    if mixer == "conv":
        p["conv"] = {
            "in_proj": _spec((D, 3 * D), bf, one_chip), "filter": _spec((3, D), bf, one_chip), "out_proj": _spec((D, D), bf, one_chip),
        }
    else:
        p["attn"] = {
            "q": _spec((D, 2048), bf, one_chip), "k": _spec((D, 512), bf, one_chip), "v": _spec((D, 512), bf, one_chip),
            "o": _spec((2048, D), bf, one_chip), "q_norm": _spec((64,), f32, one_chip), "k_norm": _spec((64,), f32, one_chip),
        }
    if ffn == "dense":
        p["ffn"] = {"w1": _spec((D, 7168), bf, one_chip), "w3": _spec((D, 7168), bf, one_chip), "w2": _spec((7168, D), bf, one_chip)}
    else:
        p["moe"] = _expert_layer(one_chip)
    return p


def test_a_conv_block_with_its_dense_ffn_makes_no_product_twice(one_chip, cut, monkeypatch):
    """Layer 0 of the cut, forwards and backwards at the cell's shapes: five products forwards and two transposes
    for each going backwards, 15 ``convolution``s, none of them under ``rematted_computation``. The parent's
    formulation (a plain ``jax.checkpoint`` with no policy) makes ``in_proj``, ``out_proj``, ``w1`` and ``w3`` again: 19."""
    monkeypatch.setattr(lm, "on_tpu", lambda: True)
    p, x = _block(one_chip, "conv", "dense"), _spec((B, T, D), jnp.bfloat16, one_chip)
    remade = ("rematted_computation/lm.conv/dot_general", "rematted_computation/lm.dense_ffn/dot_general")

    def compiled_block():  # traced anew for each formulation
        fn = jax.value_and_grad(lambda p, x: lm._layer(p, x, cut, "conv", "dense")[0].astype(jnp.float32).sum(), argnums=(0, 1))
        compiled = _compile(fn, p, x)
        return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes

    text, temp = compiled_block()
    assert len(re.findall(r" convolution\(", text)) == 15
    assert not any(name in text for name in remade)
    # The kept products are 0.74 GB of bfloat16 (201 + 67 + 2 x 235 MB), and the plan is smaller for them: 1,108,875,264 B
    # here against the parent's 1,311,196,672 (my compiles, PR 32), which holds the second copies while the transposes run.
    assert temp < 1.2e9
    monkeypatch.setattr(lm, "KEEP_PRODUCTS", None)  # the parent's: `jax.checkpoint(whole)`
    parent_text, _ = compiled_block()
    assert len(re.findall(r" convolution\(", parent_text)) == 19
    assert all(name in parent_text for name in remade)


def test_the_attention_block_around_the_flash_kernel_makes_no_projection_twice(one_chip, cut, monkeypatch):
    """The branch a TPU takes through the attention block (two halves around the flash kernel), lowered and not
    compiled: going backwards neither half makes ``q``, ``k``, ``v`` or ``o``'s product again; the router's small
    float32 product is still made again (the expert layer keeps its inputs only)."""
    monkeypatch.setattr(lm, "on_tpu", lambda: True)
    p, x = _block(one_chip, "attn", "moe"), _spec((B, T, D), jnp.bfloat16, one_chip)

    def lowered_block():
        fn = jax.grad(lambda p, x: lm._layer(p, x, cut, "attn", "moe")[0].astype(jnp.float32).sum(), argnums=(0, 1), allow_int=True)
        with jax.default_matmul_precision("high"):
            return jax.jit(fn).lower(p, x).as_text(debug_info=True)

    text = lowered_block()
    assert "rematted_computation/lm.attn/dot_general" not in text
    assert "rematted_computation/lm.moe.route/dot_general" in text
    monkeypatch.setattr(lm, "KEEP_PRODUCTS", None)
    assert "rematted_computation/lm.attn/dot_general" in lowered_block()
