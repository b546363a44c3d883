"""Brook-2PL quickstart: chop analysis -> deadlock-free locking -> sweep.

Run: PYTHONPATH=src python examples/brook_quickstart.py

Brook-2PL (Habibi et al.) lands as ``DynParams`` flags, so the happy
path is the same 3 lines as every other protocol::

    w = WorkloadSpec(kind="zipf", txn_len=4, n_rows=2048, zipf_s=0.9)
    s = simulate("brook2pl", w, n_threads=64, horizon=120_000)
    print(extract("brook2pl", 64, s).tps)

This smoke additionally asserts the protocol's structural claims (used
by the CI ``brook-smoke`` job): zero deadlock-detection ticks, zero
deadlock (forced) rollbacks, a drained system with the serializability
counter invariant intact, and a win over mysql-2PL in the deadlock
regime — then shows the ``chop`` analysis the ordering comes from and
a bit-exact brook sweep lane.
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp

from repro.core.lock import (HALT, WorkloadSpec, chop, extract, simulate)
from repro.sweep import grid, run_sweep
from repro.compile_cache import enable_compile_cache

W = WorkloadSpec(kind="zipf", txn_len=4, n_rows=2048, zipf_s=0.9)
TPCC = WorkloadSpec(kind="tpcc", txn_len=10, n_rows=8192,
                    n_warehouses=4, write_ratio=0.6)
T = 64
HORIZON = 120_000


def main():
    # 1. the static analysis Brook-2PL runs on (per workload template)
    print(chop.chop(TPCC).describe())
    print()

    # 2. deadlock regime head-to-head: multi-row writes on a hot zipf set
    results = {}
    for proto in ("mysql", "brook2pl"):
        s = simulate(proto, W, n_threads=T, horizon=HORIZON, drain=True)
        r = extract(proto, T, s)
        results[proto] = r
        print(f"{proto:9s} tps={r.tps:9.0f} deadlock_aborts="
              f"{r.forced_aborts} dd_ticks={r.dd_ticks}")
        leftover = int(jnp.abs(s.rows.applied_val
                               - s.rows.committed_val).sum())
        assert bool((s.th.phase == HALT).all()), f"{proto}: did not drain"
        assert leftover == 0, f"{proto}: serializability violated"

    b, m = results["brook2pl"], results["mysql"]
    assert b.forced_aborts == 0, "brook2pl rolled back a deadlock victim"
    assert b.dd_ticks == 0, "brook2pl paid deadlock-detection ticks"
    assert b.commits > m.commits, "brook2pl must beat mysql under skew"
    print(f"# brook2pl/mysql commits: {b.commits / max(m.commits, 1):.2f}x,"
          " zero deadlock aborts, zero detection ticks")

    # 3. the sweep substrate carries brook2pl like any other protocol —
    #    vmapped lanes stay bit-identical to the simulate() calls above
    pts = grid(["mysql", "brook2pl"], W, T, horizon=HORIZON, drain=True,
               name_fmt="{protocol}_T{n_threads}")
    res = run_sweep(pts, chunk_size=2)
    for proto in ("mysql", "brook2pl"):
        got = res[f"{proto}_T{T}"]
        want = results[proto]
        assert (got.commits, got.iters, got.tps, got.dd_ticks) == \
            (want.commits, want.iters, want.tps, want.dd_ticks), proto
    print(f"# sweep parity ok ({res.n_compiles} compile(s) this run)")
    print("brook-quickstart-ok")


if __name__ == "__main__":
    enable_compile_cache()
    main()
