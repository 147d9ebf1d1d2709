"""The on-chip benchmark's entry point.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell. Everything that belongs to one cell is data found by the
names in ``BENCHMARK.json``: the configuration (``configs/<config>.json``, its
plain reference ``reference/<reference>.py``), the traffic mix
(``traffic/<traffic>.json``, which names its driver ``drivers/<driver>.py``) and
the per-layer metrics (``metrics/<name>.py``). The last line of standard output
is the result; without a TPU the run fails and prints none. ``--rehearse-cpu``
runs the same path at the configuration's tiny ``rehearse_*`` sizes to debug the
harness: it exits 3 and is never a pass.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List

T_START = time.perf_counter()  # set-up is counted from here
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import ROOT, load_module, peak_for, resolve_cell  # noqa: E402


def device_check(chips: int, rehearse: bool):
    """The devices the cell runs on, or exit: no accelerator is no result."""
    import jax

    devices = jax.devices()
    if rehearse:
        return devices[:chips]
    if devices[0].platform != "tpu":
        print(f"chip benchmark: needs a TPU, JAX found platform {devices[0].platform!r}", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"chip benchmark: the cell needs {chips} chips, JAX sees {len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def per_layer_metrics(cell: Dict[str, Any], run: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric through its own reader; a reader that finds
    nothing to read returns None and the metric is left out of the line."""
    out = {}
    for m in cell["per_layer"]:
        value = load_module("metrics", m["name"], cell["here"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv: List[str]) -> int:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true", help="tiny sizes on the CPU; exits 3, never a pass")
    args = parser.parse_args(argv)

    cell = resolve_cell(args.workload)
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)  # the system under test; fails in a directory that holds only the benchmark
    import sheeprl_tpu  # noqa: F401  (also places JAX's compile cache: JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache)

    devices = device_check(int(cell["chips"]), args.rehearse_cpu)
    driver = load_module("drivers", cell["traffic_file"]["driver"], cell["here"])
    run = driver.run(
        cell=cell,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        rehearse=args.rehearse_cpu,
        devices=devices,
        t_start=T_START,
        out_dir=os.path.join(cell["here"], "out"),
    )
    run["peak"] = None if args.rehearse_cpu else peak_for(devices[0].device_kind, cell["here"])
    run["cell"] = cell

    if args.trace:
        metrics = per_layer_metrics(cell, run)
    else:
        metrics = {m["name"]: {"value": float(run["end_to_end"][m["name"]]), "unit": m["unit"]} for m in cell["end_to_end"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": run["memory_peak_bytes"],
    }
    result: Dict[str, Any] = {
        "correct": bool(run["check"]["correct"]),
        "attempted": run["attempted"],
        "failed": run["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = run["trace"]["breakdown"]
    result["counters"] = run.get("counters", {})  # the last train call's scalars, where the driver keeps them
    result["steps"] = run["steps"]
    result["compared"] = run["check"]["compared"]  # each number beside its limit, last in the line
    sys.stdout.flush()
    for name, pair in run["check"]["compared"].items():
        print(f"compared {name}: {pair['value']!r} limit {pair['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse_cpu:
        print("REHEARSAL on the CPU at tiny sizes: " + json.dumps(result), file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
