import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import dataclasses  # noqa: E402
import json  # noqa: E402

import pytest  # noqa: E402

# a size the CPU backend runs in seconds; the chip runs the real ones
TINY = {
    "hotspot-group-closed": dict(n_rows=4096, n_threads=64, horizon=100_000,
                                 traffic={}),
    "zipf-mysql-serve": dict(n_rows=4096, n_threads=64, horizon=80_000,
                             traffic={"boundaries": 8, "quiet_boundaries": 3,
                                      "rate_per_tick": 0.002,
                                      "warm_boundaries": 2}),
}


def tiny(name: str):
    """The cell ``name`` at a CPU test size, every other setting as run."""
    from bench import spec
    cell = spec.load_cell(name)
    size = TINY[name]
    cfg = json.loads(json.dumps(cell.config))
    cfg["workload"]["n_rows"] = size["n_rows"]
    cfg["n_threads"] = size["n_threads"]
    cfg["horizon"] = size["horizon"]
    return dataclasses.replace(cell, config=cfg,
                               traffic={**cell.traffic, **size["traffic"]})


@pytest.fixture
def tiny_cell():
    return tiny
