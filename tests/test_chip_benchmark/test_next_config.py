"""What the next PR that adds to the benchmark may count on, on the CPU.

The first test is the contract of ``BENCHMARK.json``'s lists, one case a clause: accepted entries of
``per_layer``, ``workloads`` and ``configs`` keep their fields and their place, new ones come after them,
a metric with a ``workloads`` list is reported by exactly those cells, and every cell reports at least
one per-layer metric that has a reader. The accepted entries are written here as a PREFIX of each list,
never as the whole of it. This is the one place where the order of entries is asserted: every other
test of the benchmark checks membership, so that appending an entry breaks none of them.

The others rehearse a further language-model configuration in a temporary copy of the benchmark: new
files and appended entries only (``models/lm.py``'s blocks under another layer pattern, a count file
of its own with one more scope, a reader of its own, a cell), found by name, counted, run and read
without an edit to a file that is there, with every cell file's checks of the lists run on the copy.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, CHIP)

import common  # noqa: E402

FIELDS = ("name", "unit", "better", "source", "layer", "moves")
ACCEPTED = (  # in their places; a later `benchmark` PR may lengthen this, and nobody shortens it
    ("sample_wait_ms", "ms", "lower", "program_span", "buffer sample and H2D", "gsteps_per_s"),
    ("dispatch_ms", "ms", "lower", "program_span", "train dispatch", "gsteps_per_s"),
    ("fence_wait_ms", "ms", "lower", "program_span", "train program", "gsteps_per_s"),
    ("player_sync_ms", "ms", "lower", "program_span", "player sync", "gsteps_per_s"),
    ("train_device_ms", "ms", "lower", "device_trace", "train program", "gsteps_per_s"),
    ("train_mfu_pct", "%", "higher", "host_clock", "train program", "gsteps_per_s"),
    ("device_idle_pct", "%", "lower", "device_trace", "device", "gsteps_per_s"),
    ("device_peak_hbm_gib", "GiB", "lower", "program_counter", "device", "gsteps_per_s"),
    ("setup_compile_s", "s", "lower", "program_counter", "compile management", "setup_s"),
    ("window_compiles", "count", "lower", "program_counter", "compile management", "gsteps_per_s"),
    ("prefetch_wait_ms", "ms", "lower", "program_span", "buffer sample and H2D", "gsteps_per_s"),
    ("prefetch_sample_ms", "ms", "lower", "program_span", "buffer sample and H2D", "gsteps_per_s"),
    ("prefetch_h2d_ms", "ms", "lower", "program_span", "buffer sample and H2D", "gsteps_per_s"),
    ("h2d_mib_per_step", "MiB", "lower", "program_counter", "buffer sample and H2D", "gsteps_per_s"),
    ("train_route_ms", "ms", "lower", "program_counter", "train dispatch", "gsteps_per_s"),
    ("train_execute_ms", "ms", "lower", "program_counter", "train dispatch", "gsteps_per_s"),
    ("setup_lower_s", "s", "lower", "program_counter", "compile management", "setup_s"),
    ("lm_train_mfu_pct", "%", "higher", "host_clock", "train program", "gsteps_per_s"),
    ("moe_device_ms", "ms", "lower", "device_trace", "expert layer", "gsteps_per_s"),
    ("mixer_device_ms", "ms", "lower", "device_trace", "token mixers", "gsteps_per_s"),
    ("head_loss_device_ms", "ms", "lower", "device_trace", "head and loss", "gsteps_per_s"),
    ("moe_experts_roofline_pct", "%", "higher", "device_trace", "expert layer", "gsteps_per_s"),
    ("moe_load_max_over_mean", "ratio", "lower", "program_counter", "expert layer", "gsteps_per_s"),
    ("rollout_feed_ms", "ms", "lower", "program_span", "rollout feed", "gsteps_per_s"),
    ("flash_attention_roofline_pct", "%", "higher", "device_trace", "token mixers", "gsteps_per_s"),
    ("gmm_roofline_pct", "%", "higher", "device_trace", "expert layer", "gsteps_per_s"),
    ("moe_compact_share", "ratio", "higher", "program_counter", "expert layer", "gsteps_per_s"),
    ("window_attention_roofline_pct", "%", "higher", "device_trace", "token mixers", "gsteps_per_s"),
    ("window_attention_device_ms", "ms", "lower", "device_trace", "token mixers", "gsteps_per_s"),
    ("shared_expert_device_ms", "ms", "lower", "device_trace", "expert layer", "gsteps_per_s"),
    ("moe_route_device_ms", "ms", "lower", "device_trace", "expert layer", "gsteps_per_s"),
    ("setup_import_s", "s", "lower", "program_counter", "set-up", "setup_s"),
    ("setup_build_s", "s", "lower", "program_counter", "set-up", "setup_s"),
    ("setup_trace_s", "s", "lower", "program_counter", "set-up", "setup_s"),
    ("setup_xla_compile_s", "s", "lower", "program_counter", "set-up", "setup_s"),
    ("setup_cache_load_s", "s", "lower", "program_counter", "set-up", "setup_s"),
    ("setup_harness_s", "s", "lower", "program_counter", "set-up", "setup_s"),
)
WORKLOAD_FIELDS = ("name", "config", "traffic", "chips")
ACCEPTED_WORKLOADS = (  # in their places, as ACCEPTED
    ("dv3_xl.chip_player", "dv3_xl_crafter", "chip_player", 1),
    ("lfm2_ep4.ppo_update_8k", "lfm2_8b_a1b_ep4", "ppo_update_8k", 1),
    ("trinity_ep16.ppo_update_8k", "trinity_mini_ep16", "ppo_update_8k", 1),
    ("smallthinker_ep8.ppo_update_16k", "smallthinker_21b_ep8", "ppo_update_16k", 1),
)
CONFIG_FIELDS = ("name", "file")
ACCEPTED_CONFIGS = (  # in their places, as ACCEPTED
    ("dv3_xl_crafter", "benchmarks/chip/configs/dv3_xl_crafter.json"),
    ("lfm2_8b_a1b_ep4", "benchmarks/chip/configs/lfm2_8b_a1b_ep4.json"),
    ("trinity_mini_ep16", "benchmarks/chip/configs/trinity_mini_ep16.json"),
    ("smallthinker_21b_ep8", "benchmarks/chip/configs/smallthinker_21b_ep8.json"),
)
NAMES = tuple(entry[0] for entry in ACCEPTED)
# what only a learner with a replay prefetcher and DV3's count has to read
DV3_ONLY = ("train_mfu_pct", "prefetch_wait_ms", "prefetch_sample_ms", "prefetch_h2d_ms", "h2d_mib_per_step")
LM_METRICS = NAMES[17:26]  # the nine that any language-model configuration's cell reports
SETUP = NAMES[31:37]  # the six set-up readers, which every cell reports
_LM_SHARED = tuple(n for n in NAMES[:27] if n not in DV3_ONLY)  # the twelve outside readers, the nine, `moe_compact_share`
ACCEPTED_CELLS = {  # the accepted metrics each accepted cell reports, in the list's order
    "dv3_xl.chip_player": NAMES[:17] + SETUP,
    "lfm2_ep4.ppo_update_8k": _LM_SHARED + SETUP,
    "trinity_ep16.ppo_update_8k": _LM_SHARED + ("window_attention_roofline_pct", "window_attention_device_ms", "shared_expert_device_ms") + SETUP,
    "smallthinker_ep8.ppo_update_16k": _LM_SHARED + ("window_attention_roofline_pct", "window_attention_device_ms", "moe_route_device_ms") + SETUP,
}


def _list_checks():
    """Every other test file's ``LIST_CHECKS`` here: its checks of what ``BENCHMARK.json``'s lists say of its entries, by
    membership, each a function of a checkout's root. Found by name, so that a new cell's test file joins with no edit here."""
    here, checks = os.path.dirname(os.path.abspath(__file__)), []
    for f in sorted(os.listdir(here)):
        if not f.startswith("test_") or not f.endswith(".py") or f == os.path.basename(__file__):
            continue
        path = os.path.join(here, f)
        module = sys.modules.get(f[:-3])
        if getattr(module, "__file__", None) != path:  # a file this process did not collect: load it beside pytest's modules
            spec = importlib.util.spec_from_file_location(f"_list_checks_{f[:-3]}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
        checks += getattr(module, "LIST_CHECKS", ())
    return tuple(checks)


def _reported(root):
    """cell -> the per-layer metrics it reports, as the harness resolves ``<root>/BENCHMARK.json``."""
    bench = common.load_json(root, "BENCHMARK.json")
    return {w["name"]: [m["name"] for m in common.resolve_cell(w["name"], root)["per_layer"]] for w in bench["workloads"]}


def _keep_their_fields_and_their_place(entries, fields, accepted):
    assert tuple(tuple(e[k] for k in fields) for e in entries[: len(accepted)]) == accepted
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)  # so a new entry is a new name, and comes after the accepted ones


def accepted_entries_keep_their_fields_and_their_place(root):
    entries = common.load_json(root, "BENCHMARK.json")["per_layer"]
    _keep_their_fields_and_their_place(entries, FIELDS, ACCEPTED)
    assert all(set(m) - {"workloads"} == set(FIELDS) for m in entries)  # an entry has these keys and, at most, a list


def accepted_workloads_keep_their_fields_and_their_place(root):
    _keep_their_fields_and_their_place(common.load_json(root, "BENCHMARK.json")["workloads"], WORKLOAD_FIELDS, ACCEPTED_WORKLOADS)


def accepted_configs_keep_their_fields_and_their_place(root):
    _keep_their_fields_and_their_place(common.load_json(root, "BENCHMARK.json")["configs"], CONFIG_FIELDS, ACCEPTED_CONFIGS)


def accepted_cells_keep_their_metrics_and_new_ones_come_after(root):
    reported = _reported(root)
    for cell, names in ACCEPTED_CELLS.items():
        assert tuple(reported[cell][: len(names)]) == names, cell
        assert not set(reported[cell][len(names) :]) & set(NAMES), cell


def a_listed_metric_is_reported_by_exactly_its_cells(root):
    bench, reported = common.load_json(root, "BENCHMARK.json"), _reported(root)
    moved = {m["name"]: {w for w in reported if w in m.get("workloads", reported)} for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        listed = m.get("workloads")
        if listed is not None:
            assert listed and len(set(listed)) == len(listed) and set(listed) <= set(reported), m
            assert set(listed) <= moved[m["moves"]], m  # each listed cell reports the end-to-end metric it moves
        want = set(reported if listed is None else listed) & moved[m["moves"]]
        assert {cell for cell, names in reported.items() if m["name"] in names} == want, m
        assert want, m  # no entry that no cell reports


def every_cell_reports_a_per_layer_metric_and_each_has_a_reader(root):
    here = os.path.join(root, common.load_json(root, "BENCHMARK.json")["paths"][0])
    for cell, names in _reported(root).items():
        assert names, cell
        for name in names:
            assert callable(common.load_module("metrics", name, here).read), (cell, name)


CLAUSES = (
    accepted_entries_keep_their_fields_and_their_place,
    accepted_workloads_keep_their_fields_and_their_place,
    accepted_configs_keep_their_fields_and_their_place,
    accepted_cells_keep_their_metrics_and_new_ones_come_after,
    a_listed_metric_is_reported_by_exactly_its_cells,
    every_cell_reports_a_per_layer_metric_and_each_has_a_reader,
)


@pytest.mark.parametrize("clause", CLAUSES, ids=lambda f: f.__name__)
def test_per_layer_entries_keep_the_contract(clause):
    clause(ROOT)


def test_the_contract_refuses_what_it_forbids(tmp_path):
    """Each clause against an edit of its kind: a changed field, an entry put before an accepted one (in each of the
    three lists), a key beyond the contract's, a name twice, a list that takes a metric from an accepted cell, a list
    that names no cell, a metric without a reader."""
    os.symlink(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks")

    def edited(edit):
        bench = common.load_json(ROOT, "BENCHMARK.json")
        edit(bench)
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
        return str(tmp_path)

    new = {"name": "fence_ms", "unit": "ms", "better": "lower", "source": "program_span", "layer": "train program", "moves": "gsteps_per_s"}
    cell = {"name": "dv3_xl.sync8", "config": "dv3_xl_crafter", "traffic": "chip_player", "chips": 1, "why": "x"}
    config = {"name": "dv3_xl_sync8", "source": "x", "file": "benchmarks/chip/configs/dv3_xl_crafter.json", "reduced": [], "why": "x"}
    for clause, edit, error in (
        (accepted_entries_keep_their_fields_and_their_place, lambda b: b["per_layer"][3].update(better="higher"), AssertionError),
        (accepted_entries_keep_their_fields_and_their_place, lambda b: b["per_layer"].insert(0, new), AssertionError),
        (accepted_entries_keep_their_fields_and_their_place, lambda b: b["per_layer"].append({**new, "why": "x"}), AssertionError),
        (accepted_workloads_keep_their_fields_and_their_place, lambda b: b["workloads"].insert(3, cell), AssertionError),
        (accepted_workloads_keep_their_fields_and_their_place, lambda b: b["workloads"][1].update(chips=4), AssertionError),
        (accepted_configs_keep_their_fields_and_their_place, lambda b: b["configs"].insert(0, config), AssertionError),
        (accepted_configs_keep_their_fields_and_their_place, lambda b: b["configs"].append(dict(b["configs"][2])), AssertionError),
        (accepted_cells_keep_their_metrics_and_new_ones_come_after, lambda b: b["per_layer"][1].update(workloads=["dv3_xl.chip_player"]), AssertionError),
        (a_listed_metric_is_reported_by_exactly_its_cells, lambda b: b["per_layer"][0].update(workloads=["no.such_cell"]), AssertionError),
        (a_listed_metric_is_reported_by_exactly_its_cells, lambda b: b["per_layer"][0].update(workloads=[]), AssertionError),
        (every_cell_reports_a_per_layer_metric_and_each_has_a_reader, lambda b: b["per_layer"].append(new), FileNotFoundError),
    ):
        with pytest.raises(error):
            clause(edited(edit))


# ------------------------------------------------------------------ a further language-model configuration
NEW_CONFIG, NEW_CELL, NEW_METRIC = "throwaway_lm", "throwaway_lm.ppo_update_8k", "throwaway_opt_device_ms"
COUNT_FILE = '''"""A throwaway count file: the accepted language-model count's parts, and one more scope that it lists as uncounted."""
import os

from common import load_module

_lm = load_module("", "flops_lfm2", os.path.dirname(os.path.abspath(__file__)))
UNCOUNTED = _lm.UNCOUNTED + ("ppo.extra",)
LAYERS = {**_lm.LAYERS, "optimizer": ("ppo.opt", "ppo.extra")}
kernels = _lm.kernels


def step_flops(sizes, pairs_here=None):
    return _lm.lfm2_step_flops(sizes, pairs_here)
'''
READER = '''"""Device self time a step under the scopes the configuration's count file lists for the layer ``optimizer``."""
from common import load_module


def read(run):
    return load_module("", "scopes", run["cell"]["here"]).layer_ms(run, "optimizer")
'''


def _files(here):
    out = {}
    for dp, dirs, fs in os.walk(here):
        dirs[:] = [d for d in dirs if d not in ("out", "__pycache__")]  # run-time products
        for f in fs:
            with open(os.path.join(dp, f), "rb") as fh:
                out[os.path.relpath(os.path.join(dp, f), here)] = fh.read()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A checkout's worth in a temporary directory: ``BENCHMARK.json``, ``benchmarks/chip`` and the program
    (a link), then what a ``model_config`` PR brings: three new files and appended entries."""
    root = str(tmp_path_factory.mktemp("checkout"))
    here = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(CHIP, here, ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "sheeprl_tpu"), os.path.join(root, "sheeprl_tpu"))
    before = _files(here)

    config = common.load_json(here, "configs", "lfm2_8b_a1b_ep4.json")
    config.update(name=NEW_CONFIG, flops="flops_throwaway:step_flops")
    config["overrides"] = config["overrides"] + ["algo.lm.layers=[0,2,4]"]  # conv + dense; attention and conv with expert layers
    config["sizes"]["layers"] = [0, 2, 4]
    with open(os.path.join(here, "configs", f"{NEW_CONFIG}.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(here, "flops_throwaway.py"), "w") as f:
        f.write(COUNT_FILE)
    with open(os.path.join(here, "metrics", f"{NEW_METRIC}.py"), "w") as f:
        f.write(READER)

    bench = common.load_json(ROOT, "BENCHMARK.json")
    bench["configs"].append({"name": NEW_CONFIG, "source": "x", "file": f"benchmarks/chip/configs/{NEW_CONFIG}.json", "reduced": config["reduced"], "why": "x"})
    bench["workloads"].append({"name": NEW_CELL, "config": NEW_CONFIG, "traffic": "ppo_update_8k", "chips": 1, "why": "x"})
    for m in bench["per_layer"]:  # its cell comes to report a shared metric by joining the metric's list
        if m["name"] in LM_METRICS + ("moe_compact_share",) + SETUP:
            m["workloads"].append(NEW_CELL)
    bench["per_layer"].append({"name": NEW_METRIC, "unit": "ms", "better": "lower", "source": "device_trace", "layer": "optimizer", "moves": "gsteps_per_s", "workloads": [NEW_CELL]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    yield {"root": root, "here": here, "before": before}
    # whatever a test of this file loaded from the copy is gone with it
    for name in [n for n, m in sys.modules.items() if n.startswith("chipbench_") and str(getattr(m, "__file__", "")).startswith(root)]:
        del sys.modules[name]


def _env():
    # the copy's runs share this checkout's compile cache (the program places it by its own path otherwise)
    return {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": os.environ.get("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))}


def test_new_configuration_is_found_by_name_and_counted_with_nothing_loaded_first(copy):
    root, here = copy["root"], copy["here"]
    checks = _list_checks()
    found = {os.path.basename(check.__code__.co_filename) for check in checks}
    assert {"test_trinity_cell.py", "test_smallthinker_cell.py", "test_setup_readers.py"} <= found
    for check in CLAUSES + checks:  # the appended entries keep the contract, and every test file's checks of its entries hold
        check(root)
    cell = common.resolve_cell(NEW_CELL, root)
    assert cell["here"] == here and cell["config_file"]["sizes"]["layers"] == [0, 2, 4]
    reported = [m["name"] for m in cell["per_layer"]]
    assert set(LM_METRICS + ("moe_compact_share", NEW_METRIC) + SETUP) <= set(reported)
    assert not set(reported) & set(DV3_ONLY) and reported[:12] == list(ACCEPTED_CELLS["lfm2_ep4.ppo_update_8k"][:12])
    assert NEW_METRIC not in _reported(root)["lfm2_ep4.ppo_update_8k"]
    # a fresh interpreter that loads flops.py and nothing else finds each configuration's count, the new one's too
    code = (
        "import json, sys; sys.path.insert(0, sys.argv[1]); import common\n"
        "flops = common.load_module('', 'flops', sys.argv[1])\n"
        "assert not [m for m in sys.modules if m.startswith('chipbench_') and m != 'chipbench__flops']\n"
        "out = {}\n"
        "for name in sys.argv[2:]:\n"
        "    config = common.load_json(sys.argv[1], 'configs', name + '.json')\n"
        "    out[name] = [flops.step_flops(config), list(flops.scopes_of(config)), list(flops.layer_scopes(config, 'optimizer'))]\n"
        "print(json.dumps(out))\n"
    )
    proc = subprocess.run([sys.executable, "-c", code, here, NEW_CONFIG, "lfm2_8b_a1b_ep4", "dv3_xl_crafter"], capture_output=True, text=True, timeout=120, cwd=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout)
    assert round(out["dv3_xl_crafter"][0] / 1e12, 3) == 8.955 and round(out["lfm2_8b_a1b_ep4"][0] / 1e12, 2) == 21.26
    assert 0 < out[NEW_CONFIG][0] < out["lfm2_8b_a1b_ep4"][0]  # three of its five layers
    assert out[NEW_CONFIG][1] == out["lfm2_8b_a1b_ep4"][1] + ["ppo.extra"] and out[NEW_CONFIG][2] == ["ppo.opt", "ppo.extra"]
    assert out["lfm2_8b_a1b_ep4"][2] == [] and out["dv3_xl_crafter"][2] == []


@pytest.mark.timeout(900)
def test_new_cell_rehearses_through_the_copy_s_entry_point(copy):
    proc = subprocess.run(
        [sys.executable, os.path.join(copy["here"], "run.py"), "--workload", NEW_CELL, "--seed", str(2**31 + 99), "--seconds", "1", "--trace", "0", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=800, cwd=copy["root"], env=_env(),
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    result = json.loads(next(l for l in proc.stderr.splitlines() if l.startswith("REHEARSAL")).split(": ", 1)[1])
    assert result["correct"] and result["failed"] == 0 and result["steps"]["in_window"] >= 1
    assert set(result["metrics"]) == {"gsteps_per_s", "step_ms_p95", "setup_s"}
    assert result["counters"]["Moe/pairs_total"] == 2 * 32 * 2 * 2  # tokens x experts a token x TWO expert layers: its own pattern ran
    assert 0.0 <= result["counters"]["Moe/compact_share"] <= 1.0 and list(result)[-1] == "compared"


@pytest.mark.timeout(900)
def test_new_cell_s_traced_path_reads_the_shared_metrics_and_its_own(copy, monkeypatch):
    """``--trace 1`` on the CPU as ``test_seq_cell.py`` rehearses it (the capture has no device plane, so
    ``reduce.reduce_dir`` is stubbed), with every module loaded from the copy."""
    import jax

    here = copy["here"]
    reduce = common.load_module("", "reduce", here)
    monkeypatch.setattr(reduce, "reduce_dir", lambda d: {"busy_s": 0.3, "window_s": 0.5, "n_devices": 1, "breakdown": {"device_ops": [], "idle_gaps": []}})
    cell = common.resolve_cell(NEW_CELL, copy["root"])
    run = common.load_module("drivers", "seq_learner", here).run(
        cell=cell, seed=2**31 + 4321, seconds=0.5, trace=True, rehearse=True,
        devices=jax.devices()[:1], t_start=time.perf_counter(), out_dir=os.path.join(here, "out"),
    )
    run.update(peak=None, cell=cell)
    assert run["check"]["correct"] and run["scopes"] is None and run["trace"]["breakdown"]["device_ms_a_step_by_scope"] == []

    def read():
        return {m["name"]: common.load_module("metrics", m["name"], here).read(run) for m in cell["per_layer"]}

    values = read()
    assert values["rollout_feed_ms"] > 0 and values["moe_load_max_over_mean"] >= 1.0 and 0.0 <= values["moe_compact_share"] <= 1.0
    device = set(LM_METRICS) - {"rollout_feed_ms", "moe_load_max_over_mean"} | {NEW_METRIC}
    assert all(values[name] is None for name in device)  # device numbers: nothing on a CPU
    # with a reduction by its scopes and a peak, all nine and its own read as numbers
    run["peak"] = common.peak_for("TPU v5 lite")
    run["counters"]["Moe/pairs_here"] = 32768.0
    run["scopes"] = {
        "steps": 10.0,
        "scopes": {"lm.moe.experts": 0.5, "lm.moe.route": 0.2, "lm.attn": 0.9, "lm.conv": 0.4, "lm.head": 0.3, "ppo.loss": 0.05, "ppo.opt": 0.1, "ppo.extra": 0.02},
        "kernels": {"lm.attn": 0.6, "lm.moe.experts": 0.4},
    }
    run["steps"]["in_window"], run["window_s"] = 10, 4.0
    again = read()
    assert again[NEW_METRIC] == pytest.approx(12.0) and again["moe_device_ms"] == pytest.approx(70.0)
    assert again["mixer_device_ms"] == pytest.approx(130.0) and again["head_loss_device_ms"] == pytest.approx(35.0)
    flops = common.load_module("", "flops", here)
    least = flops.kernel_least(cell["config_file"], "gmm", 32768.0)
    assert again["gmm_roofline_pct"] == pytest.approx(100 * (least["flops"] / 197e12) / 0.04) and again["moe_experts_roofline_pct"] == pytest.approx(again["gmm_roofline_pct"] * 0.8)
    assert 0 < again["flash_attention_roofline_pct"] < 100
    assert again["lm_train_mfu_pct"] == pytest.approx(100 * flops.step_flops(cell["config_file"], 32768.0) * 10 / 4.0 / 197e12)
    assert all(again[name] is not None for name in LM_METRICS + ("moe_compact_share", NEW_METRIC))


def test_no_file_that_the_benchmark_had_differs_in_the_copy(copy):
    """Last in this file: after the new configuration was found, counted, run and read."""
    after = _files(copy["here"])
    assert set(after) - set(copy["before"]) == {f"configs/{NEW_CONFIG}.json", "flops_throwaway.py", f"metrics/{NEW_METRIC}.py"}
    assert all(after[f] == copy["before"][f] for f in copy["before"])
    assert copy["before"] == _files(CHIP)  # and the copy was this checkout's benchmark
