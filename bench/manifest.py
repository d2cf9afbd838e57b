"""``BENCHMARK.json`` and the files it names, resolved for one cell.

Everything that belongs to one configuration, traffic mix, metric or
cell lives in files of its own, found by the name the manifest gives:

* ``bench/configs/<config>.json`` (sizes) and ``<config>.py`` (weights,
  data, the program's loss, the plain reference loss, FLOP counts);
* ``bench/traffic/<traffic>.json`` (cohort, engine, chunk, codecs, ...);
* ``bench/metrics/<metric>.py`` (one ``compute(ctx)`` each);
* ``bench/limits/<cell>.json`` (the limit of each number the output
  check compares).

A cell, configuration or metric is added by adding files and manifest
entries; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path


def load_module(path: Path, name: str):
    """Import one file of the benchmark under a name of its own."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(path)
    return json.loads(path.read_text())


@dataclass
class Metric:
    name: str
    unit: str
    entry: dict
    module: object = field(repr=False)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    spec: dict                 # the configuration's file, as run
    traffic: dict
    limits: dict
    config: object = field(repr=False)     # the configuration's module
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _metrics_for(entries: list, cell: str, bench: Path) -> list:
    out = []
    for entry in entries:
        if "workloads" in entry and cell not in entry["workloads"]:
            continue
        mod = load_module(bench / "metrics" / f"{entry['name']}.py",
                          f"bench_metric_{entry['name'].replace('.', '_')}")
        out.append(Metric(entry["name"], entry["unit"], entry, mod))
    return out


def load(root: Path) -> dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def resolve(root: Path, cell_name: str) -> Cell:
    """The cell ``cell_name`` of the manifest under ``root`` with every
    file it names loaded. Raises ``KeyError`` for an unknown cell and
    ``FileNotFoundError`` for a missing file."""
    manifest = load(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json")
    w = cells[cell_name]
    bench = Path(root) / "bench"
    configs = {c["name"]: c for c in manifest["configs"]}
    conf = configs[w["config"]]
    spec = _read_json(Path(root) / conf["file"])
    module = load_module(Path(root) / conf["file"].replace(".json", ".py"),
                         f"bench_config_{w['config']}")
    return Cell(
        name=cell_name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"], spec=spec,
        traffic=_read_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(bench / "limits" / f"{cell_name}.json"),
        config=module,
        end_to_end=_metrics_for(manifest["end_to_end"], cell_name, bench),
        per_layer=_metrics_for(manifest["per_layer"], cell_name, bench))
