"""The on-chip benchmark's own tests: quick, on the CPU, no chip and no TPU topology call.

The first group checks the data the harness is driven by. The last two tests keep the
control and the planted faults of ``correct`` (PERF.md, "How the limits were set")
at a size a test run can hold: the tiny ``rehearse_*`` sizes at ``32-true``, where
the program and its plain reference agree to rounding, so one limit separates the
sound run from the control (the reference in bfloat16, the nearest precision below
float32) and from each fault.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, CHIP)

import common  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench():
    return common.load_json(ROOT, "BENCHMARK.json")


def test_benchmark_json_names_and_units():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in b["configs"]] + [w["name"] for w in b["workloads"]]
    names += [w["traffic"] for w in b["workloads"]] + [k for c in b["configs"] for k in c["reduced"]]
    metrics = b["end_to_end"] + b["per_layer"]
    for n in names + [m["name"] for m in metrics]:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1 for m in b["end_to_end"])
    assert all(m["moves"] in e2e for m in b["per_layer"])
    assert {w["config"] for w in b["workloads"]} == {c["name"] for c in b["configs"]}


def test_every_cell_has_its_files():
    b = bench()
    for w in b["workloads"]:
        cell = common.resolve_cell(w["name"])
        config, traffic = cell["config_file"], cell["traffic_file"]
        assert config["name"] == w["config"] and sorted(config["reduced"]) == sorted(
            next(c["reduced"] for c in b["configs"] if c["name"] == w["config"])
        )
        for kind, name in (("drivers", traffic["driver"]), ("reference", config["reference"])):
            assert os.path.isfile(os.path.join(CHIP, kind, name + ".py")), (kind, name)
        assert cell["per_layer"], "a cell reports at least one per-layer metric"
        for m in cell["per_layer"]:
            assert callable(common.load_module("metrics", m["name"]).read)
        assert callable(common.load_module("", "flops").count_of(config)[1])  # found from the configuration file alone
    peaks = common.load_json(CHIP, "peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12 and peaks["TPU v5 lite"]["source"]
    with pytest.raises(KeyError):
        common.peak_for("TPU v9 imaginary")


def test_flops_equal_the_original_and_the_issue():
    from benchmarks.analytic_flops import dv3_step_flops as original
    from sheeprl_tpu.config import compose

    flops = common.load_module("", "flops")
    cfg = compose(config_name="config", overrides=["exp=dreamer_v3"])  # DV3-S, Atari-100K recipe
    sizes = {
        "cnn_channels_multiplier": 32, "recurrent_state_size": 512, "stochastic_size": 32, "discrete_size": 32,
        "dense_units": 512, "mlp_layers": 2, "horizon": 15, "image": 64, "batch": 16, "sequence": 64,
        "transition_hidden": 512, "representation_hidden": 512, "actions": 6, "bins": 255,
    }
    assert flops.dv3_step_flops(sizes) == original(cfg, 16, 64, (6,))
    xl = common.load_json(CHIP, "configs", "dv3_xl_crafter.json")
    assert round(flops.step_flops(xl) / 1e12, 3) == 8.955


def test_reduce_known_busy_idle_split():
    reduce = common.load_module("", "reduce")
    ops = [("fusion.1", 0.0, 1.0), ("fusion.2", 0.5, 2.0), ("copy", 4.0, 5.0), ("fusion.1", 7.0, 8.0)]
    spans = [("dispatch", 0.0, 2.0), ("fence", 2.0, 2.5), ("player_sync", 2.5, 4.0), ("sample", 5.0, 5.5), ("x", 9.5, 10.0)]
    out = reduce.summarize({"/device:TPU:0": ops}, spans)
    assert out["window_s"] == 10.0 and out["busy_s"] == 4.0
    assert dict(map(tuple, out["breakdown"]["device_ops"])) == {"fusion.1": 2.0, "fusion.2": 1.5, "copy": 1.0}
    # gaps: 2-4 (player_sync covers 1.5 of 2), 5-7 (sample covers 0.5 of 2 -> other), 8-10 (other)
    assert dict(map(tuple, out["breakdown"]["idle_gaps"])) == {"other": 4.0, "player_sync": 2.0}
    assert reduce.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    with pytest.raises(ValueError):
        reduce.summarize({"/device:TPU:0": []}, spans)


def test_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = bench()["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT,
    )
    assert proc.returncode not in (0, 3), proc.stderr[-2000:]
    assert proc.stdout.strip() == "", proc.stdout[-2000:]
    assert "needs a TPU" in proc.stderr


def test_new_cell_metric_and_reader_are_found_without_an_edit(tmp_path):
    """What a later PR does: new files and new entries, no edit of a file that is there."""
    root = str(tmp_path)
    shutil.copytree(CHIP, os.path.join(root, "benchmarks", "chip"), ignore=shutil.ignore_patterns("out", "__pycache__"))
    here = os.path.join(root, "benchmarks", "chip")
    before = {f: open(os.path.join(dp, f), "rb").read() for dp, _, fs in os.walk(here) for f in fs}
    b = bench()
    config = common.load_json(here, "configs", "dv3_xl_crafter.json")
    config["name"] = "dv3_xl_sync8"
    with open(os.path.join(here, "configs", "dv3_xl_sync8.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(here, "traffic", "sync8.json"), "w") as f:
        json.dump({"name": "sync8", "driver": "learner", "overrides": ["algo.player_sync_every=8"],
                   "warmup_steps": 3, "episode_len_mean": 180}, f)
    with open(os.path.join(here, "metrics", "fence_ms.py"), "w") as f:
        f.write("def read(run):\n    rows = [b - a for n, a, b in run['spans'] if n == 'fence']\n"
                "    return 1e3 * sum(rows) / len(rows) if rows else None\n")
    b["configs"].append({"name": "dv3_xl_sync8", "source": "x", "file": "benchmarks/chip/configs/dv3_xl_sync8.json",
                         "reduced": ["buffer.size"], "why": "x"})
    b["workloads"].append({"name": "dv3_xl.sync8", "config": "dv3_xl_sync8", "traffic": "sync8", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "fence_ms", "unit": "ms", "better": "lower", "source": "program_span",
                           "layer": "train program", "moves": "gsteps_per_s", "workloads": ["dv3_xl.sync8"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    cell = common.resolve_cell("dv3_xl.sync8", root=root)
    assert cell["traffic_file"]["overrides"] == ["algo.player_sync_every=8"] and cell["here"] == here
    assert "fence_ms" in [m["name"] for m in cell["per_layer"]]
    old = common.resolve_cell(b["workloads"][0]["name"], root=root)
    assert "fence_ms" not in [m["name"] for m in old["per_layer"]]
    reader = common.load_module("metrics", "fence_ms", here)
    assert reader.read({"spans": [("fence", 0.0, 0.25), ("fence", 1.0, 1.75), ("sample", 0, 9)]}) == 500.0
    assert reader.read({"spans": []}) is None  # nothing to read: the metric is left out of the line
    after = {f: open(os.path.join(dp, f), "rb").read() for dp, _, fs in os.walk(here) for f in fs}
    assert all(after[f] == before[f] for f in before)


# ---------------------------------------------------------------- correct, its control and its faults
TEST_LIMIT = 1e-3  # at 32-true the sound run reads under 1e-4; the control and the faults read over 5e-3
GROUP_NUMBERS = [f"{kind}.{g}" for kind in ("grad_gap", "delta_gap", "grad_diff", "grad_diff_leaf") for g in ("world_model", "actor", "critic")]


def _cell_at_f32():
    cell = common.resolve_cell("dv3_xl.chip_player")
    config = cell["config_file"]
    config["overrides"] = config["overrides"] + ["fabric.precision=32-true"]
    config["precision"] = "32-true"
    config["limits"] = {"rows_wrong": 0, "world_model.step1": TEST_LIMIT, **{k: TEST_LIMIT for k in GROUP_NUMBERS}}
    return cell


def _run(monkeypatch, fault=None):
    """The rest of a run with the look for a chip skipped: the driver's own ``run``,
    with ``fault`` planted under the timed path."""
    import time

    import jax

    learner = common.load_module("drivers", "learner")
    faults = common.load_module("", "faults")
    build = learner.build

    def broken(*a, **k):
        built = build(*a, **k)
        built["train_fn"] = faults.FAULTS[fault](built["train_fn"])
        return built

    with monkeypatch.context() as m:
        if fault is not None:
            m.setattr(learner, "build", broken)
        return learner.run(
            cell=_cell_at_f32(), seed=2**31 + 12345, seconds=0.5, trace=False, rehearse=True,
            devices=jax.devices()[:1], t_start=time.perf_counter(), out_dir=os.path.join(CHIP, "out"),
        )


def _failed(verdict):
    return [k for k, v in verdict["compared"].items() if v["limit"] is not None and not v["value"] <= v["limit"]]


@pytest.mark.timeout(900)
def test_correct_passes_the_sound_run_and_fails_the_control(monkeypatch):
    check = common.load_module("", "check")
    reference = common.load_module("reference", "dv3")
    probes = []
    compare = check.Probe.compare
    monkeypatch.setattr(check.Probe, "compare", lambda self, config: probes.append(self) or compare(self, config))
    out = _run(monkeypatch)
    verdict = out["check"]
    assert verdict["correct"] and set(verdict["compared"]) == set(_cell_at_f32()["config_file"]["limits"]), verdict
    assert out["steps"]["in_window"] > 0 and out["end_to_end"]["gsteps_per_s"] > 0
    # the control: the reference in the nearest precision below float32, put in the program's place
    # (the same build, the same rows and keys)
    probe = probes[0]
    numbers = check.gaps(probe.reference_readings(quant=reference.fake_bf16), probe.reference_readings())
    control = check.judge({"rows_wrong": 0.0, **numbers}, _cell_at_f32()["config_file"]["limits"])
    assert not control["correct"] and "grad_diff_leaf.world_model" in _failed(control), control["compared"]


@pytest.mark.timeout(900)
def test_correct_fails_each_fault_planted_under_the_timed_path(monkeypatch):
    for fault in ("half_batch", "state_unchanged"):
        verdict = _run(monkeypatch, fault=fault)["check"]
        assert verdict["compared"]["rows_wrong"]["value"] == 0
        assert not verdict["correct"] and "grad_diff_leaf.world_model" in _failed(verdict), (fault, verdict["compared"])
