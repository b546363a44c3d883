"""Sweep-subsystem quickstart: a whole protocol x threads grid in 3 lines.

Run: PYTHONPATH=src python examples/sweep_quickstart.py

The grid below (4 protocols x 3 thread counts over the paper's hotspot
workload) is bucketed by shape, padded, and executed as shared-compile
batched JAX programs; results are bit-identical to calling ``simulate()``
once per point. Swap in ``expand()`` for workload-field axes (Zipf skew,
write ratio), add ``p_abort=[...]`` / ``costs=[...]`` axes, or
``save_results()`` to keep a JSON record — see repro/sweep/.
"""
from repro.core.lock import WorkloadSpec
from repro.sweep import grid, run_sweep, summarize, save_results
from repro.compile_cache import enable_compile_cache

HOT = WorkloadSpec(kind="hotspot_update", txn_len=1, n_rows=512)


def main():
    # The 3-line sweep: grid -> run_sweep -> summarize.
    pts = grid(["mysql", "o2", "group", "bamboo"], HOT, [16, 64, 256],
               horizon=100_000)
    res = run_sweep(pts)
    print("\n".join(summarize(res)))

    print(f"# {len(pts)} configs, {res.n_compiles} engine compile(s), "
          f"{res.wall_s:.1f}s wall")
    save_results("/tmp/sweep_quickstart.json", res,
                 meta={"example": "sweep_quickstart"})
    print("# results JSON -> /tmp/sweep_quickstart.json")


if __name__ == "__main__":
    enable_compile_cache()
    main()
