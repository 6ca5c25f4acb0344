"""Resolve a cell of BENCHMARK.json to its files, by name.

A cell is a configuration's name plus a traffic mix's name.  Whatever
belongs to one configuration lives in `configs/<name>/` (`config.json`,
`app.siddhi`, `model.py`), a traffic mix is `traffic/<name>.json`, and a
per-layer metric is read by `layer_metrics/<name>.py` — the part of the
metric's name before its first `.`, so `x.sat` and `x.paced` share one
reader.  Adding any of them is adding files and BENCHMARK.json entries; no
file here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    sizes: dict
    app_text: str
    model: object
    traffic: dict
    end_to_end: list            # BENCHMARK.json entries this cell reports
    per_layer: list             # (entry, read function)
    rehearse: bool = False
    peaks: dict = field(default_factory=dict)


def _load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _in_cell(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def load_benchmark() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_peaks(device_kind: str) -> dict:
    """The device's published peaks; a device not in the table is an
    error, never a default."""
    table = _load_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    if device_kind not in table:
        raise SystemExit(
            f"benchmark: device kind {device_kind!r} is not in "
            f"benchmarks/harness/peaks.json ({sorted(table)}); add its "
            f"published peaks with their source before measuring on it")
    return table[device_kind]


def resolve(cell_name: str, rehearse: bool = False) -> Cell:
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"benchmark: no workload {cell_name!r} in "
                         f"BENCHMARK.json ({sorted(cells)})")
    w = cells[cell_name]
    return load_cell(
        w, os.path.join(BENCH_DIR, "configs", w["config"]),
        os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"),
        bench, rehearse)


def load_cell(w: dict, cfg_dir: str, traffic_path: str, bench: dict,
              rehearse: bool = False) -> Cell:
    """The cell a `workloads` entry `w` describes, from the configuration's
    directory and the traffic mix's file, with the metrics of `bench` that
    list it (a test's fixture is loaded from its own directory)."""
    cell_name = w["name"]
    config = _load_json(os.path.join(cfg_dir, "config.json"))
    traffic = _load_json(traffic_path)
    sizes = dict(config["sizes"])
    if rehearse:
        sizes.update(config.get("rehearse_sizes", {}))
        traffic.update(traffic.get("rehearse", {}))
    with open(os.path.join(cfg_dir, "app.siddhi")) as fh:
        app_text = fh.read().format(**sizes)
    model = _load_module(os.path.join(cfg_dir, "model.py"),
                         f"bench_model_{w['config']}")
    per_layer = []
    for entry in bench["per_layer"]:
        if not _in_cell(entry, cell_name):
            continue
        base = entry["name"].split(".", 1)[0]
        reader = _load_module(
            os.path.join(BENCH_DIR, "layer_metrics", base + ".py"),
            f"bench_layer_{base}")
        per_layer.append((entry, reader.read))
    return Cell(
        name=cell_name, chips=int(w["chips"]), config=config, sizes=sizes,
        app_text=app_text, model=model, traffic=traffic,
        end_to_end=[e for e in bench["end_to_end"]
                    if _in_cell(e, cell_name)],
        per_layer=per_layer, rehearse=rehearse)
