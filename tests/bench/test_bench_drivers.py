"""Each driver runs a cell end to end on the CPU at a tiny size, and the
command refuses to run where it cannot measure the chip."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import run, spec

KEYS = ["correct", "attempted", "failed", "metrics", "device", "window",
        "checks"]


@pytest.mark.parametrize("name", ["hotspot-group-closed", "zipf-mysql-serve"])
def test_driver_yields_a_correct_result_line(name, tiny_cell):
    cell = tiny_cell(name)
    r = run.run_cell(cell, seed=2**31 + 7, seconds=0.0, trace=False,
                     t_start=time.perf_counter())
    assert list(r) == KEYS
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in r["metrics"].values():
        assert m["value"] > 0
    assert set(r["checks"]) == set(cell.limits["limits"])
    assert r["device"]["platform"] == "cpu"
    json.dumps(r)


def _cli(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "hotspot-group-closed", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_exits_nonzero_without_a_tpu():
    p = _cli(spec.ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_cli_exits_nonzero_with_only_the_benchmark(tmp_path):
    bm = spec.benchmark()
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    for p in bm["paths"]:
        shutil.copytree(os.path.join(spec.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
