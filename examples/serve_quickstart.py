"""Quickstart: serve an open-system request stream through the lock engine.

    PYTHONPATH=src python examples/serve_quickstart.py

Poisson arrivals -> bounded admission queue -> device-resident engine
pool (``repro.serving``). Two protocols serve the same overload on the
SysBench hotspot, showing the open-system version of the paper's claim:
at high offered load the *protocol* sets the knee, so group locking
completes more requests, rejects fewer, and holds lower tails than
MySQL-style detection 2PL. Exits non-zero if any invariant breaks —
CI runs this as the serving smoke test.
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.lock import WorkloadSpec
from repro.serving import ServeCell, poisson, serve
from repro.compile_cache import enable_compile_cache

HOT = WorkloadSpec(kind="hotspot_update", txn_len=2, n_rows=4096)
T = 32
HORIZON = 200_000           # ticks (20 ms simulated)
RATE = 0.01                 # arrivals/tick = 100k offered TPS (overload)


def main():
    print("=== open-system serving: hotspot overload, 32-slot pool ===")
    sched = poisson(RATE, HORIZON, seed=3)
    cells = [
        ServeCell(name=proto, schedule=sched, workload=HOT, n_threads=T,
                  preset=proto, queue_cap=4 * T, admission="reject",
                  max_outstanding=8, sla_us=2_000.0)
        for proto in ("mysql", "group")
    ]
    res = serve(cells, seg_ticks=HORIZON // 20)

    for proto in ("mysql", "group"):
        s = res.serving[proto]
        print(f"  {proto:8s} offered {s.offered_tps:>8.0f} tps | "
              f"goodput {s.goodput_tps:>7.0f} tps | "
              f"p50 {s.p50_us:>7.1f}us p99 {s.p99_us:>7.1f}us | "
              f"rejected {s.rejected:>5d} | "
              f"SLA miss {s.sla_miss_frac:.0%}")

        # request conservation: every arrival is accounted for exactly once
        assert s.arrived == (s.rejected + s.shed + s.completed
                             + s.in_flight_end + s.qlen_end), (
            f"{proto}: conservation violated: {s.arrived} arrived vs "
            f"{s.rejected}+{s.shed}+{s.completed}+{s.in_flight_end}"
            f"+{s.qlen_end}")
        # ... and the per-boundary records sum to the same totals
        recs = res.segments[proto]
        assert sum(r["arrived"] for r in recs) == s.arrived
        assert sum(r["completed"] for r in recs) == s.completed

    m, g = res.serving["mysql"], res.serving["group"]
    # the queue is bounded and the load is an overload: backpressure
    # must actually fire
    assert m.rejected >= 1, "expected backpressure rejections under overload"
    # the knee ordering the figure claims: group locking clears more of
    # the same offered stream than detection 2PL on the hotspot
    assert g.goodput_tps > m.goodput_tps, (
        f"knee ordering violated: group {g.goodput_tps:.0f} <= "
        f"mysql {m.goodput_tps:.0f}")
    assert res.n_compiles <= 1, res.n_compiles

    print(f"  group/mysql goodput: {g.goodput_tps / m.goodput_tps:.2f}x "
          f"({res.n_compiles} compile)")
    print("serve_quickstart: OK")


if __name__ == "__main__":
    enable_compile_cache()
    main()
