"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry point a user calls
(``sheeprl_tpu.cli.run``, what ``sheeprl.py`` calls), in ONE process:

1. DreamerV3-S at its published width and the Atari-100K recipe shape (512-unit
   GRU, CNN multiplier 32, ``bf16-mixed``, batch 16 x sequence 64, 64x64x3 pixels
   from the dummy env; weights random from the seed): 256 prefill steps, then
   64 gradient steps.
2. PPO CartPole: eight iterations of the host-env loop, the sharded handoff,
   the fused update.

Both with the default ``compile.aot``, ``fabric.player_on_host`` and
``fabric.accelerator``. Then it checks, by the repo's own counters, that the
train programs really ran on the accelerator and that nothing was quietly
replaced on the way (no retrace, no AOT fallback, no warmup error, no
non-finite skip, native gather built, every logged loss finite).

    python chip_smoke.py               # one chip, however many are visible
    python chip_smoke.py --devices 4   # data-parallel over a 4-chip mesh
    python chip_smoke.py --rehearse-cpu   # tiny CPU rehearsal: NEVER a pass

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` only when
JAX's default backend is a TPU and every check held. Without an accelerator it
exits non-zero and prints no result. Timings printed here are smoke readings,
not performance measurements.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import logging
import math
import os
import shutil
import struct
import sys
import time
from typing import Any, Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))

_COMMON = [
    "env.sync_env=True",
    "env.capture_video=False",
    "checkpoint.save_last=False",
    "checkpoint.every=999999",
    "metric.log_level=1",
    "root_dir=chip_smoke",
]

_DV3 = [
    "exp=dreamer_v3",
    "env=dummy",
    "env.id=discrete_dummy",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.mlp_keys.encoder=[]",
    "algo.replay_ratio=1",
    "algo.run_test=False",
    "buffer.size=4096",
    "buffer.checkpoint=False",
    "run_name=dv3",
]

_PPO = ["exp=ppo", "run_name=ppo"]

# --rehearse-cpu only: cut the width and the shapes so the same code path runs
# on the CPU in about a minute. A rehearsal proves the script, never the chip.
_DV3_TINY = [
    "algo.dense_units=16",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=16",
    "algo.world_model.representation_model.hidden_size=16",
    "algo.world_model.transition_model.hidden_size=16",
    "algo.world_model.stochastic_size=4",
    "algo.world_model.discrete_size=4",
    "algo.per_rank_batch_size=4",
    "algo.per_rank_sequence_length=8",
    "algo.horizon=4",
]


def _plan(devices: int, rehearse: bool) -> Dict[str, Any]:
    """Override lines and the expected train-call counts for ``devices`` chips."""
    dv3_envs = devices  # one env per chip
    per_iter = dv3_envs * devices  # policy steps per DV3 loop iteration
    rows_per_env = 16 if rehearse else 256  # prefill rows each env must hold (>= sequence length)
    grad_steps = 8 if rehearse else 64  # per rank; replay_ratio=1 grants `dv3_envs` per iteration
    train_iters = max(1, grad_steps // dv3_envs)
    learning_starts = rows_per_env * per_iter
    dv3 = _DV3 + _COMMON + [
        f"fabric.devices={devices}",
        f"env.num_envs={dv3_envs}",
        f"algo.learning_starts={learning_starts}",
        f"algo.total_steps={learning_starts + train_iters * per_iter}",
        f"metric.log_every={max(per_iter, (train_iters * per_iter) // 4)}",
    ]
    if rehearse:
        dv3 += _DV3_TINY
    ppo_envs, rollout = 8, (16 if rehearse else 128)
    ppo_iter_steps = ppo_envs * devices * rollout
    ppo = _PPO + _COMMON + [
        f"fabric.devices={devices}",
        f"env.num_envs={ppo_envs}",
        f"algo.rollout_steps={rollout}",
        f"algo.total_steps={8 * ppo_iter_steps}",
        f"metric.log_every={ppo_iter_steps}",
    ]
    return {
        "dv3": dv3,
        "ppo": ppo,
        "dv3_train_calls": train_iters,
        "dv3_grad_steps": train_iters * dv3_envs,
        "ppo_train_calls": 8,
    }


def _read_scalars(run_dir: str) -> Dict[str, List[Tuple[int, float]]]:
    """Every scalar the run logged, read back from its TensorBoard event files
    (plain TFRecord framing + the event proto: no TensorFlow import, which
    could reach for the chip this process holds)."""
    from tensorboard.compat.proto import event_pb2

    out: Dict[str, List[Tuple[int, float]]] = {}
    for root, _dirs, files in os.walk(run_dir):
        for fn in files:
            if "tfevents" not in fn:
                continue
            with open(os.path.join(root, fn), "rb") as f:
                data = f.read()
            pos = 0
            while pos + 12 <= len(data):
                (n,) = struct.unpack("<Q", data[pos : pos + 8])
                pos += 12
                event = event_pb2.Event.FromString(data[pos : pos + n])
                pos += n + 4
                for v in event.summary.value:
                    out.setdefault(v.tag, []).append((int(event.step), float(v.simple_value)))
    return out


_LOG_ROOT = os.path.join(HERE, "logs", "runs", "chip_smoke")


class CacheLog(logging.Handler):
    """Which programs the persistent compile cache served and which it had to
    take in, by module name, from JAX's own compiler log (DEBUG lines)."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.hits: List[str] = []
        self.misses: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = str(record.msg)
        if msg.startswith("Persistent compilation cache hit"):
            self.hits.append(str(record.args[0]))
        elif msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.misses.append(str(record.args[0]))

    def drain(self) -> Dict[str, Any]:
        """Hits by name; compiles as a count (every tiny eager op is one) plus
        the names of the repo's own programs among them."""
        own = sorted(m for m in self.misses if any(k in m for k in ("train", "packed", "guarded")))
        out = {"hit": sorted(self.hits), "compiled": len(self.misses), "compiled_own_programs": own}
        self.hits, self.misses = [], []
        return out


class Checks:
    def __init__(self) -> None:
        self.failed: List[str] = []

    def __call__(self, name: str, ok: bool, detail: Any = "") -> None:
        print(f"[{'ok' if ok else 'FAIL'}] {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)


def _placements(gfn: Any) -> List[Tuple[Tuple[int, ...], Any, int]]:
    """(global shape, sharding, top-level argument index) for every input leaf
    of every live AOT executable of ``gfn``."""
    import jax

    rows = []
    for exe in gfn.aot_executables():
        args_info, _ = exe.args_info
        shardings, _ = exe.input_shardings
        for argnum, (info_tree, sh_tree) in enumerate(zip(args_info, shardings)):
            infos = jax.tree_util.tree_leaves(info_tree)
            shs = jax.tree_util.tree_leaves(sh_tree)
            rows.extend((tuple(i.shape), s, argnum) for i, s in zip(infos, shs))
    return rows


def _check_phase(
    check: Checks,
    tag: str,
    train_name: str,
    act_name: str,
    min_calls: int,
    batch_arg: int,
    batch_axis: int,
    mesh_devices: List[Any],
    stats_before: Dict[str, Any],
    phase_t0: float,
    compile_t0: float,
) -> Dict[str, Any]:
    from sheeprl_tpu.core import compile as jax_compile
    from sheeprl_tpu.telemetry import programs

    n = len(mesh_devices)
    stats = jax_compile.process_stats()
    fn_stats = stats["functions"].get(train_name)
    check(f"{tag}: {train_name} ran", fn_stats is not None and fn_stats["calls"] >= min_calls,
          f"calls={fn_stats and fn_stats['calls']} (need >= {min_calls})")
    gfn = jax_compile.find(train_name)
    rows = _placements(gfn)
    check(f"{tag}: {train_name} dispatched an AOT executable", bool(rows), f"{len(rows)} input leaves")
    param_devs = set()
    for _shape, sh, argnum in rows:
        if argnum == 0:
            param_devs |= set(sh.device_set)
    check(
        f"{tag}: params live on the mesh's {n} accelerator device(s)",
        param_devs == set(mesh_devices) and all(d.platform == mesh_devices[0].platform for d in param_devs),
        sorted(str(d) for d in param_devs),
    )
    batch_rows = [(shape, sh) for shape, sh, argnum in rows if argnum == batch_arg]
    per_dev = {sh.shard_shape(shape)[batch_axis] * n == shape[batch_axis] for shape, sh in batch_rows}
    check(f"{tag}: each device holds B/{n} rows of the batch", bool(batch_rows) and per_dev == {True},
          f"e.g. {batch_rows[0][0]} -> {batch_rows[0][1].shard_shape(batch_rows[0][0])}" if batch_rows else "no batch leaves")
    if n > 1:
        ledger = [r for r in programs.snapshot() if r["name"] == train_name]
        by_op = (ledger[-1].get("collective") or {}).get("by_op", {}) if ledger else {}
        check(f"{tag}: {train_name} all-reduces over the mesh",
              any(op.startswith("all-reduce") for op in by_op), by_op)
    act = jax_compile.find(act_name)
    player = sorted({str(d) for _s, sh, _a in _placements(act) for d in sh.device_set}) if act else []
    first = fn_stats["first_call_s"] if fn_stats else None
    return {
        "train_calls": fn_stats and fn_stats["calls"],
        "player_device": player,
        "time_to_first_train_step_s": None if first is None else round(first - (phase_t0 - compile_t0), 2),
        "phase_seconds": round(time.perf_counter() - phase_t0, 2),
        "compile_seconds": round(stats["compile_seconds"] - stats_before["compile_seconds"], 2),
        "cache_hits": stats["cache_hits"] - stats_before["cache_hits"],
        "cache_misses": stats["cache_misses"] - stats_before["cache_misses"],
    }


def _check_logs(check: Checks, tag: str, run_name: str) -> Dict[str, float]:
    scalars = _read_scalars(os.path.join(_LOG_ROOT, run_name))
    losses = {k: v for k, v in scalars.items() if k.startswith("Loss/")}
    bad = {k: v for k, v in losses.items() if not all(math.isfinite(x) for _s, x in v)}
    check(f"{tag}: every logged Loss/* is finite", bool(losses) and not bad,
          bad or {k: round(v[-1][1], 4) for k, v in sorted(losses.items())})
    skips = scalars.get("Resilience/nonfinite_skips", [])
    check(f"{tag}: Resilience/nonfinite_skips == 0", all(x == 0 for _s, x in skips), skips or "not logged (0)")
    return {k: v[-1][1] for k, v in losses.items()}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--devices", type=int, default=1, help="chips in the data mesh (default 1)")
    parser.add_argument(
        "--rehearse-cpu",
        action="store_true",
        help="run the same path tiny on the CPU to debug the script; exits non-zero, never a pass",
    )
    args = parser.parse_args()

    os.chdir(HERE)  # logs/ and the compile cache land in the checkout
    shutil.rmtree(_LOG_ROOT, ignore_errors=True)  # this script's own runs only: read back below
    sys.path.insert(0, HERE)
    import jax

    import sheeprl_tpu

    if os.path.dirname(os.path.dirname(os.path.abspath(sheeprl_tpu.__file__))) != HERE:
        print(f"chip_smoke: sheeprl_tpu imported from {sheeprl_tpu.__file__}, not this checkout", file=sys.stderr)
        return 2
    dev0 = jax.devices()[0]
    versions = {p: importlib.metadata.version(p) for p in ("jax", "jaxlib", "libtpu")}
    print(
        f"platform={dev0.platform} device_kind={dev0.device_kind} count={len(jax.devices())} "
        f"versions={versions} JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
        f"compile_cache={jax.config.jax_compilation_cache_dir}",
        flush=True,
    )
    if dev0.platform != "tpu" and not args.rehearse_cpu:
        print(
            f"chip_smoke: needs a TPU, found platform '{dev0.platform}' "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
            file=sys.stderr,
        )
        return 2
    if args.rehearse_cpu:
        print("REHEARSAL on the CPU at a tiny size: proves the script, not the chip; never a pass", flush=True)
    if args.devices > len(jax.devices()):
        print(f"chip_smoke: --devices {args.devices} but JAX sees {len(jax.devices())}", file=sys.stderr)
        return 2

    from sheeprl_tpu import cli
    from sheeprl_tpu.core import compile as jax_compile
    from sheeprl_tpu.native import native_available

    compile_t0 = time.perf_counter()  # ~ core.compile's import clock, the zero of first_call_s
    jax_compile.install_cache_listeners()
    cache_log = CacheLog()
    jax_logger = logging.getLogger("jax._src.compiler")
    jax_logger.addHandler(cache_log)
    jax_logger.setLevel(logging.DEBUG)
    mesh_devices = jax.devices()[: args.devices]
    print(f"mesh devices (jax.devices()[:{args.devices}]): {[str(d) for d in mesh_devices]}", flush=True)
    plan = _plan(args.devices, args.rehearse_cpu)
    check = Checks()
    readings: Dict[str, Any] = {}

    for tag, train_name, act_name, calls_key, batch_arg, batch_axis in (
        ("dv3", "dv3.train", "dv3.step_packed", "dv3_train_calls", 4, 2),
        ("ppo", "ppo.train", "ppo.act_packed", "ppo_train_calls", 2, 1),
    ):
        print(f"===== {tag}: cli.run({' '.join(plan[tag])})", flush=True)
        before = jax_compile.process_stats()
        phase_t0 = time.perf_counter()
        cli.run(plan[tag])
        readings[tag] = _check_phase(
            check, tag, train_name, act_name, plan[calls_key], batch_arg, batch_axis,
            mesh_devices, before, phase_t0, compile_t0,
        )
        readings[tag]["last_losses"] = _check_logs(check, tag, tag)
        # every persistent-cache lookup of the phase, by program: on a second
        # run in the same checkout the train programs belong under "hit"
        readings[tag]["cache_lookups"] = cache_log.drain()
    readings["dv3"]["gradient_steps_per_rank"] = plan["dv3_grad_steps"]

    stats = jax_compile.process_stats()
    for key in ("retraces", "aot_fallbacks", "warmup_errors"):
        check(f"{key} == 0", stats[key] == 0, stats[key])
    check("native seq_gather built and loaded", native_available())
    peaks = {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in mesh_devices}
    if not args.rehearse_cpu:  # the CPU backend reports no memory stats
        check("peak_bytes_in_use > 0 on every mesh device", all(v > 0 for v in peaks.values()), peaks)
    readings["peak_bytes_in_use"] = peaks
    readings["cache_hits_total"] = stats["cache_hits"]
    readings["cache_misses_total"] = stats["cache_misses"]
    readings["compile_seconds_total"] = round(stats["compile_seconds"], 2)
    print("SMOKE READINGS (not performance measurements): " + json.dumps(readings, sort_keys=True), flush=True)

    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: {check.failed}", file=sys.stderr)
        return 1
    if args.rehearse_cpu:
        print("REHEARSAL finished with every check held; not a pass (no chip was involved)", flush=True)
        return 3
    print(
        json.dumps(
            {"ok": True, "device": {"platform": dev0.platform, "kind": dev0.device_kind, "count": len(jax.devices())}}
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
