"""Compaction smoke: mixed-density mini-grid, repack + parity asserted.

Run: PYTHONPATH=src python examples/compaction_smoke.py

The grid packs two churning lanes (mysql/o1 keep committing through
deadlock detection) with six deadlock-stalled ones (o2/group without
detection sit near-idle) into one forced vmap chunk — the straggler mix
the sort-then-cut chunker cannot separate. The compaction scheduler must
(a) repack at least once, (b) cut total vmapped lane-iterations >= 2x,
and (c) stay bit-identical to per-config ``simulate()`` — CI runs this
as the compaction-smoke job.
"""
from repro.core.lock import WorkloadSpec, extract, simulate
from repro.sweep import point, run_sweep
from repro.compile_cache import enable_compile_cache

ZIPF = WorkloadSpec(kind="zipf", txn_len=2, n_rows=512, zipf_s=0.9)
HORIZON = 60_000


def main():
    mk = lambda pr, t: point(pr, ZIPF, t, horizon=HORIZON,
                             name=f"{pr}_T{t}")
    pts = [mk("o1", 16), mk("mysql", 16),
           mk("o2", 16), mk("o2", 32), mk("o2", 64),
           mk("group", 16), mk("group", 32), mk("group", 64)]

    res_off = run_sweep(pts, chunk_size=8, compact=False)
    res_on = run_sweep(pts, chunk_size=8)   # compaction: default for G>1

    for p in pts:       # bit-exact vs per-config simulate(), both paths
        s = simulate(p.protocol, p.workload, p.n_threads,
                     horizon=p.horizon)
        ref = extract(p.protocol, p.n_threads, s)
        for res in (res_on, res_off):
            got = res[p.name]
            assert (got.commits, got.iters, got.tps, got.abort_rate) == \
                (ref.commits, ref.iters, ref.tps, ref.abort_rate), p.name
    assert res_on.n_repacks >= 1, res_on.n_repacks
    assert res_off.lane_iters >= 2 * res_on.lane_iters, \
        (res_off.lane_iters, res_on.lane_iters)

    print(f"# compaction smoke OK: lane_iters {res_off.lane_iters} -> "
          f"{res_on.lane_iters} "
          f"({res_off.lane_iters / res_on.lane_iters:.1f}x), "
          f"{res_on.n_repacks} repack(s), wall {res_off.wall_s:.1f}s -> "
          f"{res_on.wall_s:.1f}s")
    for b in res_on.buckets:
        print(f"# repack log (n_live, width, max_delta_iters): "
              f"{b.repack_log}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
