"""Test bootstrap: force an 8-device virtual CPU mesh BEFORE jax initializes.

Mirrors the reference's CPU-Gloo multi-process tests (tests/test_algos/test_algos.py
`devices` fixture + LT_DEVICES): here multi-device paths run on one host via
``--xla_force_host_platform_device_count=8``.
"""

import os
import sys

# The suite runs on the CPU whatever the host holds: set before jax is imported,
# the env var alone selects the backend.
os.environ["JAX_PLATFORMS"] = "cpu"
# Cache even sub-second kernels (jax's default threshold is 1s): the suite's
# many subprocess CLI drills recompile dozens of tiny CPU kernels each, and
# serving them from the shared persistent cache keeps the suite inside its
# wall-clock budget. setdefault so an explicit caller choice still wins.
os.environ.setdefault("SHEEPRL_TPU_COMP_CACHE_MIN_SECS", "0")
_flags = [
    f
    for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f
]
_flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(_flags)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", f"tests must run on the CPU mesh, got {jax.devices()}"
assert jax.device_count() == 8, f"expected 8 virtual CPU devices, got {jax.device_count()}"

import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

# Per-test wall-clock limits without the pytest-timeout dependency (reference gates
# test_algos.py at 60-180 s via pytest-timeout, tests/conftest.py:71-76; the virtual
# 8-device CPU mesh compiles slower, hence the larger default).
_ALGO_TEST_DEFAULT_TIMEOUT = 600


def pytest_configure(config):
    config.addinivalue_line("markers", "timeout(seconds): per-test wall-clock limit (SIGALRM)")
    config.addinivalue_line(
        "markers",
        "faults: fault-injection drills (failpoint registry, chaos/transport smokes); "
        "select with `-m faults`, e.g. before touching checkpoint or transport code",
    )
    config.addinivalue_line(
        "markers",
        "ingraph: in-graph vectorized env backend (envs/ingraph/) — dynamics parity "
        "against Gymnasium, zero-transfer rollout guarantees, and the smoke drill; "
        "select with `-m ingraph` before touching envs/ingraph or the fused collector",
    )
    config.addinivalue_line(
        "markers",
        "telemetry: cross-plane telemetry (sheeprl_tpu/telemetry/) — span tracer, "
        "metrics fabric, device introspection, trace-id propagation; select with "
        "`-m telemetry` before touching telemetry/ or its instrumentation seams",
    )
    config.addinivalue_line(
        "markers",
        "analysis: the JAX-invariant static analyzer (sheeprl_tpu/analysis/) — rule "
        "fixtures, call-graph reachability, baseline round-trips, and the tree-wide "
        "self-lint; select with `-m analysis` (or run scripts/lint.sh) before "
        "touching analysis/ or code the self-lint covers",
    )
    config.addinivalue_line(
        "markers",
        "fleet: the replica-fleet serving plane (serve/fleet.py + serve/router.py) — "
        "supervisor respawns and epoch fencing, failover/deadline relays, rolling "
        "certified deploys, and the preemption fan-out drill; select with `-m fleet` "
        "before touching the fleet supervisor, the router, or their drain contracts",
    )
    config.addinivalue_line(
        "markers",
        "mesh: overlap-scheduled mesh training (parallel/handoff.py + parallel/overlap.py "
        "+ the HLO collective auditor) — one-put-per-shard transfer-guard pins, "
        "microbatched gradient bit-parity on the 8-device virtual mesh, collective "
        "capture/diff gating, and the handoff/grad-sync chaos drills; select with "
        "`-m mesh` before touching the handoff, the accumulation scan, or the auditor",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running end-to-end smokes excluded from the tier-1 `-m 'not slow'` "
        "sweep; run explicitly (e.g. `-m slow`) before shipping changes they cover",
    )


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("timeout")
    seconds = int(marker.args[0]) if marker and marker.args else 0
    if not seconds and "test_algos.py" in str(getattr(item, "fspath", "")):
        seconds = _ALGO_TEST_DEFAULT_TIMEOUT
    use_alarm = (
        seconds > 0
        and hasattr(signal, "SIGALRM")
        and threading.current_thread() is threading.main_thread()
    )
    if not use_alarm:
        return (yield)

    def _on_timeout(signum, frame):
        raise TimeoutError(f"test exceeded the {seconds}s wall-clock limit")

    old = signal.signal(signal.SIGALRM, _on_timeout)
    signal.alarm(seconds)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _mapped_regions() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # not Linux: no such limit to watch
        return 0


@pytest.fixture(autouse=True)
def _bound_live_executables():
    """XLA:CPU mmaps every compiled executable, and JAX's caches plus the
    guarded-function registry keep them all: one pytest process crosses
    ``vm.max_map_count`` (65530) around the 40th end-to-end run (~3.5k regions
    each), LLVM then fails with "Cannot allocate memory" and the interpreter
    dies with SIGSEGV/SIGABRT inside the next compile. Releasing the
    executables between tests gives the regions back; the persistent cache
    makes the recompiles cheap."""
    from sheeprl_tpu.core import compile as jax_compile

    yield
    if _mapped_regions() > 30000:
        jax_compile.release_executables()


@pytest.fixture(autouse=True)
def _reset_metric_state():
    from sheeprl_tpu.telemetry import trace
    from sheeprl_tpu.utils.metric import MetricAggregator
    from sheeprl_tpu.utils.timer import timer

    yield
    MetricAggregator.disabled = False
    timer.disabled = False
    timer.reset()
    # a test that configured the span tracer must not leak it (or its
    # SHEEPRL_TPU_TRACE env mirror) into tests asserting disabled-mode behavior
    trace.disable()


@pytest.fixture()
def standard_args():
    return [
        "dry_run=True",
        "env=dummy",
        "env.num_envs=2",
        "env.sync_env=True",
        "env.capture_video=False",
        "fabric.devices=1",
        "metric.log_level=0",
        "checkpoint.save_last=False",
    ]
