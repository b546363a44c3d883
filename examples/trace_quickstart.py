"""Trace quickstart: capture a lock-event trace, check the tick books
balance, and export a Perfetto-viewable Chrome trace.

    PYTHONPATH=src python examples/trace_quickstart.py [out.json]

What this demonstrates (DESIGN.md §11):

1. ``simulate_traced`` — the same engine step, but every grant /
   wait-enter / timeout / deadlock-victim / early-release / group-join /
   commit is appended to a fixed-allocation on-device ring buffer from
   inside the ``lax.while_loop``. Capacity and the on/off switch are
   traced *data*, so tracing never recompiles, and ``trace_on=False`` is
   bit-exact with the untraced engine (checked below).
2. Tick conservation — the engine charges every thread-tick to exactly
   one TickBreakdown bin, so the bins sum to ``padded_T x elapsed``
   (asserted; this is the invariant tests/test_obs.py enforces).
3. Export — Chrome trace-event JSON. Open the output file at
   https://ui.perfetto.dev (or chrome://tracing): each worker thread is
   a track, lock waits are spans named after the contended row, commits
   and deadlock victims are instant markers. Zoom into the hottest rows
   from the wait-profile printed below and you can watch mysql's
   wait-die queue churn thread by thread.
4. Overflow semantics — a deliberately tiny capacity: the buffer keeps
   its earliest events intact and counts the rest in ``dropped`` (the
   profile then says it is a lower bound) instead of wrapping.
5. Blame (DESIGN.md §14) — the same event stream pairs each wait span
   with the transaction attempt *holding* the row, yielding a blame
   table (who caused the queueing on each hot record) and the longest
   blocking chain; the export grows per-row queue-depth counter lanes.
"""
import json
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core.lock import WorkloadSpec, simulate, extract
from repro.obs import (EV_VICTIM, blame_table, check_conservation,
                       critical_path, dump_chrome_trace, events_host,
                       make_trace, simulate_traced, wait_profile)
from repro.compile_cache import enable_compile_cache

# zipf with multi-op transactions: lock-order cycles actually form, so
# mysql's detection walk has victims to kill (hotspot_update txn_len=1
# cannot deadlock — single-lock transactions never cycle)
WL = WorkloadSpec(kind="zipf", txn_len=4, n_rows=2048, zipf_s=0.9)
T, HORIZON = 64, 120_000


def main(out_path="trace_quickstart.json"):
    print(f"=== tracing mysql on zipf(s=0.9) x{T} threads, "
          f"{HORIZON} ticks ===")
    s, tb = simulate_traced("mysql", WL, n_threads=T, horizon=HORIZON,
                            cap=65_536)
    r = extract("mysql", T, s)

    # 1. the books balance: every tick of every (padded) thread is in
    # exactly one breakdown bin
    pad_t = int(s.th.phase.shape[0])
    check_conservation(s, pad_t)
    total = sum(r.breakdown.values())
    print(f"tick conservation: sum(breakdown) = {total} "
          f"= {pad_t} threads x {total // pad_t} ticks  OK")
    print("breakdown:", {k: v for k, v in r.breakdown.items() if v})

    # 2. the trace saw real contention, including deadlock victims
    ev = events_host(tb)
    n_victims = int(np.sum(ev["ev"][:ev["n"]] == EV_VICTIM))
    print(f"events: {ev['n']} stored, {ev['dropped']} dropped, "
          f"{n_victims} deadlock victims, {r.commits} commits")
    assert n_victims >= 1, "expected deadlock victims under mysql/zipf"

    # 3. export for Perfetto (with top-4 hotspot queue-depth counter
    # lanes) and sanity-check the JSON round-trips
    dump_chrome_trace(out_path, ev, label="mysql zipf quickstart",
                      hotspot_lanes=4)
    with open(out_path) as f:
        doc = json.load(f)
    assert doc["traceEvents"], "empty trace"
    assert all("ph" in e and "ts" in e for e in doc["traceEvents"]
               if e["ph"] != "M")
    print(f"wrote {out_path} ({len(doc['traceEvents'])} trace events) — "
          "open it at https://ui.perfetto.dev")

    print("\n" + wait_profile(ev, top_k=8))

    # 3b. blame: pair every wait span with the holding transaction
    # attempt — who to kill, not just where it hurts — plus the longest
    # blocking chain threading through the capture
    end = int(s.g.now)
    print("\n" + blame_table(ev, top_k=8, end=end))
    path = critical_path(ev, end=end)
    if path:
        hops = " -> ".join(f"t{h['tid']}@r{h['row']}" for h in path[:6])
        print(f"critical path: {len(path)} hops, "
              f"{sum(h['dur'] for h in path)} blocked ticks: {hops}")

    # 4. overflow: a 64-event buffer on the same run keeps its first 64
    # events bit-identical to the big capture and counts the rest
    _, tb_small = simulate_traced("mysql", WL, n_threads=T,
                                  horizon=HORIZON, cap=64, alloc=65_536)
    ev_s = events_host(tb_small)
    assert ev_s["n"] == 64 and ev_s["dropped"] > 0
    for col in ("ts", "tid", "row", "ev"):
        assert np.array_equal(ev_s[col], ev[col][:64]), col
    print(f"\noverflow demo: cap=64 kept the first 64 events intact, "
          f"dropped {ev_s['dropped']}")

    # 5. trace_on=False is the stock engine, bit for bit
    s_off, _ = simulate_traced("mysql", WL, n_threads=T, horizon=HORIZON,
                               cap=65_536, trace_on=False)
    s_ref = simulate("mysql", WL, n_threads=T, horizon=HORIZON)
    for a, b in zip(jax_leaves(s_off), jax_leaves(s_ref)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    print("trace_on=False parity with simulate(): bit-exact  OK")


def jax_leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


if __name__ == "__main__":
    enable_compile_cache()
    main(*sys.argv[1:2])
