"""sheeprl_tpu: a TPU-native (JAX/XLA/pjit/Pallas) deep-RL framework.

Re-implements the full capability surface of sonnygeorge/sheeprl (PPO/A2C/SAC/DreamerV3
families + dream_and_ponder) with a TPU-first architecture: pure-functional jitted
train steps, `lax.scan` recurrences, data-parallel sharding over a `jax.sharding.Mesh`
with XLA collectives over ICI, and host-side numpy replay buffers feeding HBM.
"""

from time import perf_counter as _perf_counter

_T_IMPORT = _perf_counter()  # the set-up phase "import" runs from here to the end of this file

import os  # noqa: E402

__version__ = "0.1.0"

ROOT_DIR = os.path.dirname(os.path.abspath(__file__))

# Persistent XLA compilation cache: the first compile of the jitted train steps
# costs tens of seconds on TPU; later processes reuse the compiled executables.
# ONE name places it: where JAX_COMPILATION_CACHE_DIR is set JAX already reads
# it and nothing here (or in core/compile.py, or any config key) sets a
# directory. Unset, the cache goes to one fixed path inside the checkout — the
# path is part of the cache key, so a directory that moves never hits. Disable
# with JAX's own JAX_ENABLE_COMPILATION_CACHE=false.
COMPILE_CACHE_DIR = os.path.join(os.path.dirname(ROOT_DIR), ".jax_cache")

import jax  # noqa: E402

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
_min_secs = os.environ.get("SHEEPRL_TPU_COMP_CACHE_MIN_SECS")
if _min_secs is not None:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", float(_min_secs))

# the set-up record and JAX's compile events live beside the compile counters;
# from here on a compile anywhere in the process is counted
from sheeprl_tpu.core import compile as _compile  # noqa: E402

_compile.record_setup_phase("import", _T_IMPORT, _perf_counter())
