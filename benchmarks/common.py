"""Shared benchmark plumbing. Every figure module exposes
``run(quick=True) -> list[str]`` of CSV rows ``name,us_per_call,derived``.

Two measurement paths:
  * ``cc_point``  — one config, one ``simulate()`` call (legacy / odd
    one-off points).
  * ``sweep_rows`` — a whole grid through ``repro.sweep`` (one compile per
    shape bucket, vmapped lanes); ``us_per_call`` is the per-point
    amortized wall time of the batched execution.
"""
from __future__ import annotations

import sys
import time

from repro.core.lock import (simulate, extract, simulate_aria, extract_aria,
                             WorkloadSpec, CostModel)
from repro.core.lock.metrics import bench_row
from repro.sweep import run_sweep, summarize


def cc_point(proto, workload, threads, horizon, costs=None, name=None,
             **kw):
    """One CC-engine measurement -> (csv_row, SimResult)."""
    t0 = time.perf_counter()
    if proto == "aria":
        s = simulate_aria(workload, threads, costs=costs, horizon=horizon)
        r = extract_aria(threads, s)
    else:
        s = simulate(proto, workload, n_threads=threads, horizon=horizon,
                     costs=costs, **kw)
        r = extract(proto, threads, s)
    wall_us = (time.perf_counter() - t0) * 1e6
    return bench_row(name or f"{proto}_T{threads}", wall_us, r), r


# Per-module sweep accounting: every sweep_rows() call appends a stats
# record here; benchmarks/run.py pops them into the module's JSON entry so
# the perf trajectory (BENCH_run.json) tracks compiles, wall, and the
# compaction scheduler's repack counts across PRs.
_SWEEP_STATS: list[dict] = []


def sweep_stats(res) -> dict:
    return {
        "n_points": len(res.points),
        "n_compiles": res.n_compiles,
        "wall_s": res.wall_s,
        "lane_iters": res.lane_iters,
        "n_repacks": res.n_repacks,
        "n_calls": sum(b.n_chunks for b in res.buckets),
        "compacted": any(b.compacted for b in res.buckets),
    }


def pop_sweep_stats() -> list[dict]:
    out, _SWEEP_STATS[:] = list(_SWEEP_STATS), []
    return out


def sweep_rows(points, names=None, **sweep_kw):
    """Run a grid through the sweep subsystem -> (csv_rows, SweepResults)."""
    res = run_sweep(points, **sweep_kw)
    _SWEEP_STATS.append(sweep_stats(res))
    return summarize(res, names), res


def emit(rows):
    for r in rows:
        print(r)
    sys.stdout.flush()
    return rows
