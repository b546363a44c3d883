"""Closed loop: back-to-back ``simulate()`` calls of one protocol.

Traffic keys: ``protocol``, ``warm_horizon`` (ticks of the warm-up call,
same executable: the horizon is traced), ``attrib``. The configuration
gives the shapes and the simulated ticks per call (``horizon``). Call k
takes the workload seed ``call_seed(--seed, k)``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import calls, reference


def _simulate(ctx, seed: int, horizon: int):
    from repro.core.lock import simulate
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    spec = calls.workload_spec(cfg, seed)
    s = simulate(tr["protocol"], spec, cfg["n_threads"],
                 costs=calls.costs(cfg), horizon=horizon,
                 attrib=bool(tr.get("attrib", False)))
    jax.block_until_ready(s)
    return s, spec


def run(ctx: calls.Context) -> calls.Outcome:
    from repro.obs import compile_log
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    horizon = int(cfg["horizon"])
    _simulate(ctx, calls.call_seed(ctx.seed, -1), int(tr["warm_horizon"]))
    ctx.setup_done()

    before = compile_log.snapshot()
    finals, numbers, commits = [], [], 0
    traced_iters = 0
    t0 = time.perf_counter()
    k = 0
    while k == 0 or time.perf_counter() - t0 < ctx.seconds:
        seed = calls.call_seed(ctx.seed, k)
        if k == 0:
            ctx.tracer.start()
        with ctx.tracer.span("call"):
            s, spec = _simulate(ctx, seed, horizon)
        n = int(s.g.commits)
        if k == 0:
            ctx.tracer.stop()
            traced_iters = int(s.g.iters)
        commits += n
        finals.append(calls.keep_final(s, seed, spec.hot_base))
        numbers.append(_host_numbers(ctx, s, n))
        del s
        k += 1
    wall = time.perf_counter() - t0
    return calls.Outcome(
        e2e={"sim_txn_per_s": commits / wall},
        attempted=k, failed=0, finals=finals, numbers=numbers,
        counters={"window_compiles": calls.window_compiles(before),
                  "traced_iters": traced_iters, "calls": k,
                  "commits": commits, "window_s": wall})


def _host_numbers(ctx, s, commits: int) -> dict:
    """Numbers read off one call besides the reference replay: threads that
    never committed, and the gap to the hot-row chain where it applies."""
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    T = cfg["n_threads"]
    txn = np.asarray(jax.device_get(s.th.txn))[:T]
    out = {"idle_clients": int((txn == 0).sum())}
    wl = cfg["workload"]
    if wl["kind"] == "hotspot_update" and wl["txn_len"] == 1:
        want = cfg["horizon"] / reference.chain_ticks(
            tr["protocol"], T, cfg["costs"])
        out["oracle_gap"] = abs(commits - want) / want
    return out
