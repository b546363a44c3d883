"""Sweep offered rates of a serve cell once, to place its fixed rate.

    python3 bench/knee.py --workload zipf-mysql-serve --rates 0.0005,0.001 --seed 7

For each rate, one ``serve()`` call of the cell's shape with arrivals over
the whole horizon (no quiet tail) prints one JSON line: requests arrived,
completed, rejected, the queue and in-flight at the end, the response-time
tail, and the wall time per boundary. The knee is the highest rate whose
queue does not grow over the horizon; the cell runs at about four fifths
of it. Needs a TPU, like ``bench/run.py``.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import calls, run, spec, tracing
    from bench.drivers import serve as drv
    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU", file=sys.stderr)
        return 1
    run.enable_compile_cache()
    base = spec.load_cell(args.workload)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(base, traffic={
            **base.traffic, "rate_per_tick": rate, "quiet_boundaries": 0})
        ctx = calls.Context(cell=cell, seed=args.seed, seconds=0,
                            tracer=tracing.Tracer(False, ""),
                            t_start=time.perf_counter())
        if i == 0:
            drv._serve(ctx, 1, int(cell.traffic["warm_boundaries"]), None)
            drv._warm_hist(ctx)
        clock = drv.BoundaryClock(ctx.tracer)
        t0 = time.perf_counter()
        res, state, _, _ = drv._serve(ctx, calls.call_seed(args.seed, i),
                                      int(cell.traffic["boundaries"]), clock)
        sr = res.serving[cell.name]
        wall = time.perf_counter() - t0
        print(json.dumps({
            "rate_per_tick": rate, "offered_tps": sr.offered_tps,
            "arrived": sr.arrived, "completed": sr.completed,
            "rejected": sr.rejected, "qlen_end": sr.qlen_end,
            "in_flight_end": sr.in_flight_end, "p99_us": sr.p99_us,
            "max_us": sr.max_us, "goodput_tps": sr.goodput_tps,
            "iters": int(state.g.iters), "wall_s": wall,
            "boundary_ms_mean": 1e3 * wall / int(cell.traffic["boundaries"]),
            "boundary_ms_max": max(clock.intervals_ms)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
