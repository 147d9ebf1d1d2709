"""Child process for the 2-process multi-controller tests (test_multihost.py).

Run as: python tests/multihost_child.py <coordinator_port> <process_id> <num_processes> <tmpdir> [mode]

Covers, on the CPU backend over localhost (the same jax.distributed machinery a
TPU pod uses over DCN — reference counterpart: the reference's CPU-Gloo
multi-process tests, tests/test_algos/test_algos.py):
- mode "ok" (default): Runtime(multihost=True) boots against an
  externally-initialized jax.distributed (the launcher case) without raising;
  log-dir broadcast, DP gradient agreement, checkpoint write-once;
- mode "timeout": NO coordinator is listening — Runtime(multihost=True,
  coordinator_address=..., multihost_timeout_s=5) must raise the wrapped
  RuntimeError quickly instead of hanging for jax's 300 s default;
- mode "mismatch": processes boot with DIFFERENT local device counts (argv[6]);
  Runtime's homogeneity validation must raise on every process;
- mode "resume": checkpoint save (write-once) then load on both processes; the
  reloaded state must match bit-for-bit and the re-run log dir must version-bump
  on every process.

Prints one JSON line with the observed values; the parent asserts cross-process
equality.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_DEVCOUNT = sys.argv[6] if len(sys.argv) > 6 else "2"
flags = [f for f in os.environ.get("XLA_FLAGS", "").split() if "host_platform_device_count" not in f]
flags.append(f"--xla_force_host_platform_device_count={_DEVCOUNT}")
os.environ["XLA_FLAGS"] = " ".join(flags)

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sheeprl_tpu.core.runtime import enable_cpu_collectives  # noqa: E402

enable_cpu_collectives()  # gloo: CPU cross-process collectives (before backend init)


def _mode_timeout(port: int, pid: int, nproc: int) -> None:
    from sheeprl_tpu.core.runtime import Runtime

    try:
        Runtime(
            accelerator="cpu",
            devices="auto",
            multihost=True,
            coordinator_address=f"localhost:{port}",
            num_processes=nproc,
            process_id=pid,
            multihost_timeout_s=5,
        )
    except RuntimeError as e:
        print(json.dumps({"pid": pid, "raised": True, "msg": str(e)[:200]}))
        return
    print(json.dumps({"pid": pid, "raised": False}))


def _mode_mismatch(port: int, pid: int, nproc: int) -> None:
    from sheeprl_tpu.core.runtime import Runtime

    jax.distributed.initialize(f"localhost:{port}", num_processes=nproc, process_id=pid)
    try:
        Runtime(accelerator="cpu", devices=jax.local_device_count(), multihost=True)
    except RuntimeError as e:
        print(json.dumps({"pid": pid, "raised": True, "msg": str(e)[:300]}))
        return
    print(json.dumps({"pid": pid, "raised": False}))


def _mode_resume(port: int, pid: int, nproc: int, tmpdir: str) -> None:
    import jax.numpy as jnp
    import numpy as np

    from sheeprl_tpu.core.runtime import Runtime
    from sheeprl_tpu.utils.checkpoint import load_state, save_state
    from sheeprl_tpu.utils.logger import get_log_dir

    jax.distributed.initialize(f"localhost:{port}", num_processes=nproc, process_id=pid)
    runtime = Runtime(accelerator="cpu", devices=jax.device_count(), multihost=True)
    os.chdir(tmpdir)

    # ---- "first run": train state + write-once checkpoint
    log_dir_1 = get_log_dir(runtime, "mh_resume", "run")
    params = runtime.replicate(jnp.arange(4, dtype=jnp.float32))
    ckpt = os.path.join(tmpdir, "ckpt_state.ckpt")
    if runtime.is_global_zero:
        save_state(ckpt, {"params": params, "iter_num": 123})
    runtime.barrier()

    # ---- "resume": every process loads the same state; log dir version-bumps
    state = load_state(ckpt)
    log_dir_2 = get_log_dir(runtime, "mh_resume", "run")
    loaded = np.asarray(state["params"])
    print(
        json.dumps(
            {
                "pid": pid,
                "iter_num": int(state["iter_num"]),
                "loaded": loaded.reshape(-1).tolist(),
                "expected": np.arange(4, dtype=np.float32).tolist(),
                "log_dir_1": log_dir_1,
                "log_dir_2": log_dir_2,
            }
        )
    )


def main() -> None:
    port, pid, nproc, tmpdir = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
    mode = sys.argv[5] if len(sys.argv) > 5 else "ok"
    if mode == "timeout":
        return _mode_timeout(port, pid, nproc)
    if mode == "mismatch":
        return _mode_mismatch(port, pid, nproc)
    if mode == "resume":
        return _mode_resume(port, pid, nproc, tmpdir)
    jax.distributed.initialize(f"localhost:{port}", num_processes=nproc, process_id=pid)
    assert jax.process_count() == nproc, jax.process_count()

    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sheeprl_tpu.core.runtime import Runtime
    from sheeprl_tpu.utils.logger import get_log_dir

    # multihost=True with distributed already initialized (launcher case) must not raise
    runtime = Runtime(accelerator="cpu", devices=jax.device_count(), multihost=True)
    assert runtime.world_size == nproc * 2, runtime.world_size

    os.chdir(tmpdir)  # log dirs are relative to cwd
    log_dir = get_log_dir(runtime, "mh_algo", "mh_run")

    # ---- DP gradient agreement over the global mesh
    data_sharding = NamedSharding(runtime.mesh, P("data"))
    w = runtime.replicate(jnp.full((2,), 0.5, jnp.float32))
    # each process owns a DIFFERENT local slice of the global [4, 2] batch
    local = np.arange(2 * 2, dtype=np.float32).reshape(2, 2) + 100.0 * pid
    batch = jax.make_array_from_process_local_data(data_sharding, local, (4, 2))

    @jax.jit
    def grad_fn(w, x):
        return jax.grad(lambda w: jnp.mean(jnp.sum(x * w[None, :], axis=-1) ** 2))(w)

    g = grad_fn(w, batch)
    # replicated output: each process reads its own addressable replica; the parent
    # asserts the two processes report the SAME value, i.e. XLA inserted the
    # cross-process reduction (the DDP allreduce equivalent)
    g_local = np.asarray(jax.device_get(g.addressable_data(0)))

    # ---- checkpoint write-once
    ckpt = os.path.join(tmpdir, f"ckpt_shared.npz")
    if runtime.is_global_zero:
        np.savez(ckpt, w=np.asarray(jax.device_get(w)))
    runtime.barrier()
    assert os.path.exists(ckpt)

    print(
        json.dumps(
            {
                "pid": pid,
                "log_dir": log_dir,
                "grad": np.asarray(g_local).reshape(-1).round(6).tolist(),
                "ckpt_exists": os.path.exists(ckpt),
            }
        )
    )


if __name__ == "__main__":
    main()
