"""The six set-up readers: on a hand-made record, on the record a process keeps, on a parent's snapshot
(nothing to read), in every cell, which lists them, and in a traced rehearsal of a language-model cell."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, CHIP)

import common  # noqa: E402

NEW = ("setup_import_s", "setup_build_s", "setup_trace_s", "setup_xla_compile_s", "setup_cache_load_s", "setup_harness_s")
CELLS = ("dv3_xl.chip_player", "lfm2_ep4.ppo_update_8k", "trinity_ep16.ppo_update_8k", "smallthinker_ep8.ppo_update_16k")  # the accepted cells


def _read(name, run):
    return common.load_module("metrics", name).read(run)


def _run(snapshot, setup_s=40.0):
    return {"compile": {"at_window_start": snapshot}, "end_to_end": {"setup_s": setup_s}, "cell": {"here": CHIP}}


def _seconds(phases):
    out = {}
    for name, a, b in phases:
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def test_readers_on_a_hand_made_record():
    phases = [
        ("import", 0.5, 2.5), ("import.ppo_recurrent", 2.0, 2.75), ("compose", 3.0, 3.25), ("runtime", 3.25, 4.0), ("build_agent.init", 5.0, 7.0),
        ("build_agent", 4.5, 8.0), ("make_train_fn", 8.0, 8.5), ("compile.ppo_recurrent.train", 20.0, 30.0),
        ("compile.ppo_recurrent.gae", 29.0, 31.0), ("replay", 9.0, 9.5),
    ]
    totals = {"trace_seconds": 6.5, "backend_compile_seconds": 1.25, "cache_retrieval_seconds": 3.0}
    run = _run({"setup_phases": phases, "setup_seconds": _seconds(phases), **totals})
    values = {name: _read(name, run) for name in NEW}
    assert values["setup_import_s"] == 2.25  # the package's and an algorithm's, overlapping, as one union
    assert values["setup_build_s"] == pytest.approx(0.25 + 0.75 + 3.5 + 0.5)  # the child inside its parent once
    assert (values["setup_trace_s"], values["setup_xla_compile_s"], values["setup_cache_load_s"]) == (6.5, 1.25, 3.0)
    # the union: [0.5, 2.75], [3, 4], [4.5, 8.5], [9, 9.5], [20, 31] -> 18.75 s in the program's phases
    assert values["setup_harness_s"] == pytest.approx(40.0 - 18.75)


def test_a_parent_s_snapshot_has_nothing_to_read():
    run = _run({"compile_seconds": 10.8, "lower_seconds": 0.0, "functions": {}})
    assert all(_read(name, run) is None for name in NEW)


def test_readers_on_the_record_this_process_keeps(monkeypatch):
    import jax.numpy as jnp

    from sheeprl_tpu.core import compile as jax_compile

    monkeypatch.setattr(jax_compile, "_SETUP_PHASES", [])
    monkeypatch.setattr(jax_compile, "_STEADY", False)
    t_start = time.perf_counter()
    time.sleep(0.02)  # the harness's
    with jax_compile.setup_phase("compose"):
        time.sleep(0.01)
    with jax_compile.setup_phase("build_agent"):
        with jax_compile.setup_phase("build_agent.init"):
            time.sleep(0.01)
    gfn = jax_compile.guarded_jit(lambda x: jnp.cos(x) * 2.0, name="t.readers")
    gfn(jnp.ones(5))
    time.sleep(0.02)  # the harness's
    run = _run(jax_compile.process_stats(), setup_s=time.perf_counter() - t_start)
    seconds = run["compile"]["at_window_start"]["setup_seconds"]
    assert _read("setup_build_s", run) == pytest.approx(seconds["compose"] + seconds["build_agent"])
    program = seconds["compose"] + seconds["build_agent"] + seconds["compile.t.readers"]
    harness = _read("setup_harness_s", run)
    assert harness == pytest.approx(run["end_to_end"]["setup_s"] - program) and 0.04 <= harness <= run["end_to_end"]["setup_s"]
    assert _read("setup_import_s", run) == 0.0  # this record began after the program's imports
    for name in ("setup_trace_s", "setup_xla_compile_s", "setup_cache_load_s"):
        assert _read(name, run) >= 0.0
    assert _read("setup_trace_s", run) > 0.0


def the_six_are_listed_and_reported(root):
    """What ``<root>/BENCHMARK.json``'s lists say of the six, by membership and never by place: ``test_next_config.py``
    asserts the order of entries once, and runs this on a copy with a further configuration appended."""
    bench = common.load_json(root, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        entry = dict(entries[name])
        entry["workloads"] = [w for w in entry["workloads"] if w in CELLS]  # a cell appended later may join the list
        assert entry == {
            "name": name, "unit": "s", "better": "lower", "source": "program_counter", "layer": "set-up",
            "moves": "setup_s", "workloads": list(CELLS),
        }
    for w in bench["workloads"]:  # each reported where it is listed, and there alone
        reported = {m["name"] for m in common.resolve_cell(w["name"], root)["per_layer"]}
        assert {name for name in NEW if w["name"] in entries[name]["workloads"]} == set(NEW) & reported, w["name"]


LIST_CHECKS = (the_six_are_listed_and_reported,)


def test_the_six_are_listed_for_the_two_cells_and_reported_there_alone():
    the_six_are_listed_and_reported(ROOT)


REHEARSE = """
import json, sys
sys.path.insert(0, sys.argv[1])
import common
reduce = common.load_module("", "reduce")
reduce.reduce_dir = lambda d: {"busy_s": 0.3, "window_s": 0.5, "n_devices": 1, "breakdown": {"device_ops": [], "idle_gaps": []}}
run = common.load_module("", "run")
sys.exit(run.main(sys.argv[2:]))
"""


@pytest.mark.timeout(900)
def test_a_traced_rehearsal_of_a_language_model_cell_reports_the_six():
    """``--trace 1 --rehearse-cpu`` through the entry point, in a process of its own (the capture has no device
    plane on the CPU, so ``reduce.reduce_dir`` is stubbed, as the verify skill rehearses the traced path)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, "-c", REHEARSE, CHIP, "--workload", "lfm2_ep4.ppo_update_8k", "--seed", str(2**31 + 39),
         "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=800, cwd=ROOT, env=env,
    )
    assert proc.returncode == 3, proc.stderr[-3000:]
    result = json.loads(next(line for line in proc.stderr.splitlines() if line.startswith("REHEARSAL")).split(": ", 1)[1])
    metrics = {name: result["metrics"][name]["value"] for name in NEW}
    assert all(v >= 0.0 for v in metrics.values()), metrics
    assert metrics["setup_import_s"] > 0.0 and metrics["setup_build_s"] > 0.0 and metrics["setup_trace_s"] > 0.0
    setup = float(re.search(r"\[setup\]\s+([0-9.]+)s .* compared steps done", proc.stderr).group(1))  # the window starts after it
    assert metrics["setup_harness_s"] <= setup
    # the repaired counter: the train function is called through plain jit, and its lowering is read now
    assert 0.0 < result["metrics"]["setup_lower_s"]["value"] <= result["metrics"]["setup_compile_s"]["value"]
