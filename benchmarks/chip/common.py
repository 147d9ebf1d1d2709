"""What the entry point, the drivers and the tests share: where the benchmark
lives and how it finds a cell's files by name."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str, here: str = HERE):
    """``<here>/<kind>/<name>.py`` as a module: drivers, metric readers and
    references are found by file name, so a new one is a new file."""
    path = os.path.join(here, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{path} is missing: nothing named {name!r} under {kind or '.'}/")
    mod_name = f"chipbench_{kind}_{name}".replace(".", "_")
    if mod_name in sys.modules and getattr(sys.modules[mod_name], "__file__", None) == path:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def resolve_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The cell's entry of ``BENCHMARK.json`` with its configuration, its traffic
    mix and the metrics it reports, each loaded from its own file."""
    bench = load_json(root, "BENCHMARK.json")
    here = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = dict(cells[name])
    cell["config_file"] = load_json(here, "configs", f"{cell['config']}.json")
    cell["traffic_file"] = load_json(here, "traffic", f"{cell['traffic']}.json")

    def reported(metric: Dict[str, Any]) -> bool:
        return name in metric.get("workloads", [name])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if reported(m)]
    e2e = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in bench["per_layer"] if reported(m) and m["moves"] in e2e]
    cell["here"] = here
    return cell


def peak_for(device_kind: str, here: str = HERE) -> Dict[str, Any]:
    """The chip's published peaks; a kind that is not in the table is an error."""
    peaks = load_json(here, "peaks.json")
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json ({sorted(peaks)}): add it with its source")
    return peaks[device_kind]
