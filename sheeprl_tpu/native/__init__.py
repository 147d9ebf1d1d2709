"""Native (C++) host-runtime components.

The TPU compute path is JAX/XLA/Pallas; the host runtime around it — here the
replay-buffer sequence gather that feeds every Dreamer gradient step (SURVEY
hot loop #4, reference buffers.py:467-526) — is C++ compiled on first use with
the toolchain baked into the image (no pybind11: plain ``extern "C"`` + ctypes).

The shared object is built from the committed ``seq_gather.cpp`` and nothing
else, into ``native/_build/`` inside the checkout (``SHEEPRL_TPU_NATIVE_CACHE``
moves it), keyed by a source hash, so rebuilds happen only when the source
changes. Opt out entirely with ``SHEEPRL_TPU_NO_NATIVE=1``. The pure-numpy
gather stays as the tested reference and takes over when the build fails —
which is logged once, at warning level, with the compiler's stderr.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "seq_gather.cpp")
_logger = logging.getLogger("sheeprl_tpu.native")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    if os.environ.get("SHEEPRL_TPU_NO_NATIVE"):
        return None
    try:
        import platform

        with open(_SRC, "rb") as f:
            src_bytes = f.read()
        # -march=native binaries are host-specific: key the cache on the target
        # ISA too, or a shared home dir on a heterogeneous fleet serves an .so
        # with illegal instructions to older CPUs
        try:
            target = subprocess.run(
                ["g++", "-march=native", "-Q", "--help=target"], capture_output=True
            ).stdout
        except Exception:
            target = b""
        digest = hashlib.sha256(src_bytes + platform.machine().encode() + target).hexdigest()[:16]
        cache_dir = os.environ.get("SHEEPRL_TPU_NATIVE_CACHE", os.path.join(_HERE, "_build"))
        os.makedirs(cache_dir, exist_ok=True)
        so_path = os.path.join(cache_dir, f"seq_gather_{digest}.so")
        if not os.path.exists(so_path):
            tmp = so_path + f".tmp{os.getpid()}"
            subprocess.run(
                ["g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17", "-pthread", _SRC, "-o", tmp],
                check=True,
                capture_output=True,
            )
            os.replace(tmp, so_path)
        lib = ctypes.CDLL(so_path)
        lib.seq_gather.restype = None
        lib.seq_gather.argtypes = [
            ctypes.c_char_p,  # src
            ctypes.c_char_p,  # dst
            ctypes.POINTER(ctypes.c_int64),  # starts
            ctypes.POINTER(ctypes.c_int64),  # envs
            ctypes.c_int64,  # n_pairs
            ctypes.c_int64,  # B
            ctypes.c_int64,  # L
            ctypes.c_int64,  # capacity
            ctypes.c_int64,  # n_envs
            ctypes.c_int64,  # row_bytes
            ctypes.c_int32,  # n_threads
        ]
        return lib
    except Exception as e:  # toolchain missing / build failure: numpy gather takes over
        stderr = getattr(e, "stderr", None)  # CalledProcessError carries the compiler's output
        _logger.warning(
            "native seq_gather unavailable (%s: %s); falling back to the numpy gather%s",
            type(e).__name__,
            e,
            "\n" + stderr.decode(errors="replace") if stderr else "",
        )
        return None


def native_available() -> bool:
    return _get_lib() is not None


def _get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if not _TRIED:
        with _LOCK:
            if not _TRIED:
                _LIB = _build_and_load()
                _TRIED = True
    return _LIB


def _n_threads(n_pairs: int) -> int:
    cpus = os.cpu_count() or 1
    return max(1, min(8, cpus - 1, n_pairs))


def seq_gather(
    src: np.ndarray,  # [capacity, n_envs, *feat]
    starts: np.ndarray,  # [n_samples * B] int64 start indices
    envs: np.ndarray,  # [n_samples * B] int64 env indices
    n_samples: int,
    batch_size: int,
    sequence_length: int,
) -> Optional[np.ndarray]:
    """Gather sequences into ``[n_samples, L, B, *feat]``; None if unavailable.

    Semantics: ``out[n, t, b] = src[(starts[n*B+b] + t) % capacity, envs[n*B+b]]``.
    """
    lib = _get_lib()
    if lib is None:
        return None
    src = np.ascontiguousarray(src)
    feat_shape = src.shape[2:]
    row_bytes = int(np.prod(feat_shape, dtype=np.int64)) * src.dtype.itemsize
    if row_bytes == 0:
        return np.empty((n_samples, sequence_length, batch_size, *feat_shape), dtype=src.dtype)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    envs = np.ascontiguousarray(envs, dtype=np.int64)
    n_pairs = n_samples * batch_size
    out = np.empty((n_samples, sequence_length, batch_size, *feat_shape), dtype=src.dtype)
    lib.seq_gather(
        src.ctypes.data_as(ctypes.c_char_p),
        out.ctypes.data_as(ctypes.c_char_p),
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        envs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n_pairs,
        batch_size,
        sequence_length,
        src.shape[0],
        src.shape[1],
        row_bytes,
        _n_threads(n_pairs),
    )
    return out
