"""BENCHMARK.json keeps to its contract, and every name it uses is found."""
import json
import math
import os
import re

import pytest

from bench import spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bm():
    return spec.benchmark()


def test_top_level_keys(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bm["command"]) <= 32
    for word in bm["command"]:
        assert not word.startswith("/") and ".." not in word
    for p in bm["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert bm["command"][1].startswith(tuple(bm["paths"]))


def test_names_and_units(bm):
    named = bm["configs"] + bm["workloads"] + bm["end_to_end"] + \
        bm["per_layer"]
    names = [x["name"] for x in named]
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [x["name"] for x in bm[group]]
        assert len(ns) == len(set(ns))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in bm["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in bm["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    lines = [c[k] for c in bm["configs"] for k in ("source", "why")] + \
        [m["layer"] for m in bm["per_layer"]] + bm["command"]
    assert all(1 <= len(x) <= 200 and "\n" not in x and "\t" not in x
               for x in lines)


def test_end_to_end_bounds(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_run_seconds_fits_a_full_check(bm):
    rs = bm["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    cells = 24
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


def test_every_cell_finds_its_files(bm):
    configs = {c["name"] for c in bm["configs"]}
    used = {w["config"] for w in bm["workloads"]}
    assert used == configs
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert spec.driver_module(cell.driver).run
        assert cell.limits["limits"]
        for m in cell.per_layer:
            assert spec.layer_module(m["name"]).read
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer


def test_per_layer_moves_a_metric_its_cells_report(bm):
    for m in bm["per_layer"]:
        assert m["layer"] and "\n" not in m["layer"]
        for w in m.get("workloads", [x["name"] for x in bm["workloads"]]):
            cell = spec.load_cell(w)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_config_files_are_the_configurations(bm):
    for c in bm["configs"]:
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["guarantees"]
        wl = cfg["workload"]
        assert wl["n_rows"] >= 1_000_000
        assert math.isfinite(cfg["horizon"]) and cfg["horizon"] > 0
