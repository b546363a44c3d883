"""Finds a cell's files by the names in ``BENCHMARK.json``."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _checked(name: str) -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    limits: dict            # bench/limits/<cell>.json
    end_to_end: tuple       # BENCHMARK.json end_to_end entries of this cell
    per_layer: tuple        # BENCHMARK.json per_layer entries of this cell

    @property
    def driver(self) -> str:
        return _checked(self.traffic["driver"])


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bm = benchmark(root)
    entries = {w["name"]: w for w in bm["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(entries)})")
    w = entries[name]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic=_load_json(os.path.join(
            BENCH_DIR, "traffic", _checked(w["traffic"]) + ".json")),
        limits=_load_json(os.path.join(
            BENCH_DIR, "limits", _checked(name) + ".json")),
        end_to_end=tuple(m for m in bm["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bm["per_layer"] if _applies(m, name)))


def driver_module(name: str):
    return importlib.import_module(f"bench.drivers.{_checked(name)}")


def layer_module(metric: str):
    """The reader of a per-layer metric: ``bench/layers/<metric>.py``. A
    quantity split by the cells that report it (``<name>.<part>``, one
    entry per end-to-end metric it moves) is read by ``<name>.py`` unless
    the split has a file of its own."""
    name = _checked(metric)
    path = os.path.join(BENCH_DIR, "layers", name + ".py")
    if "." in name and os.path.exists(path):
        loader = importlib.util.spec_from_file_location(
            f"bench.layers.{name}", path)
        module = importlib.util.module_from_spec(loader)
        loader.loader.exec_module(module)
        return module
    return importlib.import_module(f"bench.layers.{name.split('.')[0]}")
