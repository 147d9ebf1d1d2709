"""Nothing on the main path may hide the device it runs on (PR 22).

All on the CPU: the two-virtual-device stand-in for "the player lives on a
device that is not the default one", the accelerator request that must raise,
the one name that places the compile cache, and the two scripts that must fail
on a host with no chip.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from sheeprl_tpu.core import compile as jax_compile
from sheeprl_tpu.core.runtime import Runtime

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_aot_warmed_fn_dispatches_on_a_non_default_device():
    """The host-CPU player on a TPU host, in miniature: arguments committed to
    a device that is not the default one. The warmup specs carry that
    placement, so the call dispatches the AOT executable: no raise, no
    fallback counted, no trace through the jit path."""
    other = jax.devices()[1]
    assert other != jax.devices()[0]
    gfn = jax_compile.guarded_jit(lambda p, x: p["w"] * x + 1.0, name="test.other_device")
    params = {"w": jax.device_put(jnp.arange(4.0), other)}
    x = jax.device_put(jnp.ones((4,)), other)
    warmup = jax_compile.AOTWarmup()
    warmup.add(gfn, jax_compile.specs_of(params), jax_compile.spec_like(x))
    warmup.start().wait(60)
    assert warmup.errors == []
    out = gfn(params, x)
    assert out.devices() == {other}
    assert (gfn.aot_fallbacks, gfn.traces, gfn.retraces) == (0, 0, 0)
    (exe,) = gfn.aot_executables()
    assert all(s.device_set == {other} for s in jax.tree_util.tree_leaves(exe.input_shardings))


def test_placement_the_specs_did_not_carry_is_a_counted_fallback():
    """The mismatch path does not depend on the wording of a JAX error: any
    input the executable rejects is served by the jit path and counted."""
    gfn = jax_compile.guarded_jit(lambda x: x * 2.0, name="test.counted_fallback")
    gfn.aot_compile(jax.ShapeDtypeStruct((4,), jnp.float32))  # unplaced: default device
    out = gfn(jax.device_put(jnp.ones((4,)), jax.devices()[1]))
    assert out.devices() == {jax.devices()[1]}
    assert gfn.aot_fallbacks == 1
    assert jax_compile.process_stats()["functions"]["test.counted_fallback"]["aot_fallbacks"] == 1


def test_warmup_errors_reach_process_stats():
    before = jax_compile.process_stats()["warmup_errors"]
    warmup = jax_compile.AOTWarmup()

    def boom():
        raise RuntimeError("injected")

    warmup.add_task(boom, name="test.boom")
    warmup.start().wait(60)
    assert [name for name, _e in warmup.errors] == ["test.boom"]
    assert jax_compile.process_stats()["warmup_errors"] == before + 1


def test_named_accelerator_that_is_absent_raises():
    with pytest.raises(RuntimeError, match="no 'tpu' backend"):
        Runtime(accelerator="tpu")


def _cache_dir_in_child(env_overrides):
    code = (
        "import sheeprl_tpu, jax\n"
        "from sheeprl_tpu.config import compose\n"
        "from sheeprl_tpu.core import compile as c\n"
        "c.configure(compose(config_name='config', overrides=['exp=ppo']))\n"
        "print('CACHE_DIR', jax.config.jax_compilation_cache_dir)\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_overrides)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=REPO, capture_output=True, text=True, timeout=120
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return next(ln.split(" ", 1)[1] for ln in out.stdout.splitlines() if ln.startswith("CACHE_DIR "))


def test_compile_cache_is_placed_by_one_name(tmp_path):
    """Set, ``JAX_COMPILATION_CACHE_DIR`` is where the cache is and nothing in
    the program moves it; unset, it is the fixed in-checkout path."""
    assert _cache_dir_in_child({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}) == str(tmp_path)
    assert _cache_dir_in_child({}) == os.path.join(REPO, ".jax_cache")


def test_compile_cache_dir_config_key_is_rejected():
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        jax_compile.resolve({"compile": {"cache": {"dir": "/somewhere"}}})


def test_chip_smoke_fails_without_a_chip():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode != 0
    assert "found platform 'cpu'" in out.stderr and "JAX_PLATFORMS='cpu'" in out.stderr
    assert '"ok"' not in out.stdout  # no result line


def test_bench_target_fails_without_a_chip_and_writes_no_ledger_row(tmp_path):
    ledger = tmp_path / "ledger.jsonl"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--target", "dv3", "--ledger", str(ledger)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert not ledger.exists()
    assert not any(ln.startswith("{") and json.loads(ln) for ln in out.stdout.splitlines())
