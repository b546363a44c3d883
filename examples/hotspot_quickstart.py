"""Hotspot attribution quickstart: find the records that own the wait,
name the transactions that caused it, and expose it all as live metrics.

    PYTHONPATH=src python examples/hotspot_quickstart.py [metrics.prom]

What this demonstrates (DESIGN.md §14):

1. ``simulate(..., attrib=True)`` — the engine carries a per-record
   contention accumulator (``Globals.ca``: wait ticks, grants, timeouts,
   victims, queue depth) updated inside the ``lax.while_loop``. The flag
   is traced data: flipping it never recompiles, and off-runs are
   bit-exact with the stock engine.
2. Conservation — the accumulator's wait ticks sum to the TickBreakdown's
   lock_wait bin *exactly* (both charge the same mask at the same tick),
   so the per-record ranking is a lossless decomposition of a number the
   engine already reports.
3. ``hotspot_report`` — top-K records by wait share, the Gini coefficient
   of the wait distribution, and its amplification over the zipf access
   distribution's own skew (how much the *protocol* concentrates
   contention beyond the access pattern).
4. Blame — an event trace of the same cell pairs each wait span with the
   holding transaction attempt: the blame table and critical blocking
   chain (``obs.blame``).
5. Live serving metrics — a served pool with ``attrib=True`` feeds a
   Prometheus-text-exposition registry per boundary
   (``serving.ServingMetrics``); top-K hotspot gauges ride along, and
   the exposition is scrape-able over HTTP or dumped textfile-style.
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import urllib.request

from repro.core.lock import WorkloadSpec, simulate
from repro.obs import (blame_table, check_ca_conservation, events_host,
                       hotspot_report, simulate_traced)
from repro.serving import ServeCell, ServingMetrics, poisson, serve
from repro.compile_cache import enable_compile_cache

WL = WorkloadSpec(kind="zipf", txn_len=8, n_rows=2048, zipf_s=1.2)
T, HORIZON = 64, 120_000


def main(out_path="hotspot_metrics.prom"):
    # 1+2: accumulator on, conservation exact
    print(f"=== mysql on zipf(s=1.2) x{T} threads, {HORIZON} ticks, "
          "attrib=True ===")
    s = simulate("mysql", WL, n_threads=T, horizon=HORIZON, attrib=True)
    check_ca_conservation(s)
    print("conservation: sum(ca.wait_ticks) == breakdown[lock_wait]  OK\n")

    # 3: where does the wait concentrate, and who concentrated it?
    print(hotspot_report(s, WL, top_k=8))

    # 4: the blame view of the same cell (event-trace pairing)
    s_tr, tb = simulate_traced("mysql", WL, n_threads=T, horizon=HORIZON,
                               cap=65_536, attrib=True)
    ev = events_host(tb)
    print("\n" + blame_table(ev, top_k=6, end=int(s_tr.g.now)))

    # 5: live metrics from a served pool
    reg = ServingMetrics(sla_budget=0.01, top_k=4)
    cell = ServeCell(name="pool", schedule=poisson(0.004, 60_000, seed=7),
                     workload=WL, n_threads=16, preset="mysql",
                     sla_us=500.0, attrib=True)
    serve([cell], seg_ticks=10_000, metrics_registry=reg)
    srv = reg.serve_http()          # port 0 -> pick a free one
    port = srv.server_address[1]
    scraped = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics").read().decode()
    srv.shutdown()
    assert scraped == reg.render()
    reg.dump(out_path)
    hot = [ln for ln in scraped.splitlines()
           if ln.startswith("repro_hotspot_wait_ticks{")]
    print(f"\nserving metrics: scraped {len(scraped.splitlines())} "
          f"exposition lines from :{port}, wrote {out_path}")
    for ln in hot:
        print("  " + ln)


if __name__ == "__main__":
    enable_compile_cache()
    main(*sys.argv[1:2])
