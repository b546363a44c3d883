"""Readings that set a cell's limits: sound runs, and the control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--sound] [--control]

Prints one JSON line per run: the mode, the seed and every number the
cell's check computes (the worst call of the run). ``--seconds 0`` makes
each run one call. The benchmark's own runs never run this.

The control is the program with one of its stages switched off through
its own profiler seam (``engine._make_step(..., ablate=...)``, the stand-in
that ``repro.obs.prof`` uses): the shortcut a later change could be
tempted to take. The cell's limits file names the stages
(``"control": {"ablate": [...]}``); the control must come out not correct.
"""
import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def ablated(stages):
    """Every engine program built inside runs with ``stages`` ablated."""
    from repro.core.lock import engine
    from bench.calls import ENGINE_ENTRIES
    orig = engine._make_step
    extra = frozenset(stages)

    def make_step(stat, dp, until=None, ablate=frozenset()):
        return orig(stat, dp, until=until, ablate=frozenset(ablate) | extra)

    def clear():
        for name in ENGINE_ENTRIES:
            getattr(engine, name).clear_cache()

    engine._make_step = make_step
    clear()
    try:
        yield
    finally:
        engine._make_step = orig
        clear()


def readings(cell, seed: int, seconds: float) -> dict:
    """The worst-call numbers and ``correct`` of one run of ``cell``."""
    from bench import run
    r = run.run_cell(cell, seed, seconds, trace=False,
                     t_start=time.perf_counter())
    nums = {k: c["value"] for k, c in r["checks"].items()}
    return {"correct": r["correct"], **nums,
            **{k: v["value"] for k, v in r["metrics"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--sound", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import run, spec
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    run.enable_compile_cache()
    cell = spec.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    modes = [m for m, on in (("sound", args.sound),
                             ("control", args.control)) if on]
    for mode in modes:
        ctx = (ablated(cell.limits["control"]["ablate"])
               if mode == "control" else contextlib.nullcontext())
        with ctx:
            for seed in seeds:
                out = {"mode": mode, "seed": seed}
                try:
                    out.update(readings(cell, seed, args.seconds))
                except Exception as e:  # a control that crashes has failed
                    out.update(correct=False,
                               error=f"{type(e).__name__}: {e}"[:500])
                print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
