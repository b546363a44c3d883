"""Where one call of a cell spends its time, by the program's own labels.

    python3 bench/stage_table.py --workload <cell> --seed <n>

Runs the cell as ``bench/run.py --trace 1 --seconds 0`` does (one call,
traced), then once untraced, and prints to standard error: device self
time of the engine programs per ``engine.STAGE_SCOPES`` scope (per loop
iteration and per call), the largest operations with their stage, the
share of engine op time in fusions that mix stages, host self time and
count of each ``repro.*`` span, device-idle time under each innermost
span, and the wall of the traced call against the untraced one. The
last line of standard output is the same as one JSON object, with the
traced run's per-layer metrics. Needs a TPU; the benchmark's own runs
never run this.
"""
import argparse
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP = 15


def _call_walls(path: str) -> list:
    """Seconds of each ``bench.call`` span on the trace's host planes."""
    from jax.profiler import ProfileData
    from bench import tracing
    out = []
    for plane in ProfileData.from_file(path).planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out += [e.duration_ns / 1e9 for e in line.events
                    if e.name == "bench.call"]
    return out


def table(cell, seed: int) -> dict:
    from bench import calls, program_trace, run, tracing
    from repro.obs import prof
    traced = run.run_cell(cell, seed, 0.0, trace=True,
                          t_start=time.perf_counter())
    path = tracing.Tracer(True, run.TRACE_DIR).xplane()
    pt = program_trace.read_file(path)
    jitted, args = program_trace.entry_args(cell)
    text = jitted.lower(*args).compile().as_text()
    stages, mixed = prof.op_stages(text), prof.mixed_fusions(text)
    ops = {k: t for k, t in pt.op_self.items()
           if any(p in k[0] for p in calls.ENGINE_ENTRIES)}
    total = sum(ops.values())
    per_stage = collections.defaultdict(float)
    for (_, op), t in ops.items():
        per_stage[stages.get(op, "(not in map)")] += t
    window = traced["window"]
    iters = window.get("traced_iters") or 1
    plain = run.run_cell(cell, seed, 0.0, trace=False,
                         t_start=time.perf_counter())
    rate = next(v["value"] for k, v in plain["metrics"].items()
                if k.endswith("txn_per_s"))
    return {
        "workload": cell.name, "seed": seed,
        "correct": [traced["correct"], plain["correct"]],
        "metrics": {k: v["value"] for k, v in traced["metrics"].items()},
        "traced_iters": window.get("traced_iters"),
        "traced_boundaries": window.get("traced_boundaries"),
        "engine_op_s": total,
        "stages_s": dict(sorted(per_stage.items(),
                                key=lambda kv: -kv[1])),
        "stages_us_per_iter": {str(s): 1e6 * t / iters
                               for s, t in per_stage.items()},
        "top_ops": [[m, op, stages.get(op), t, op in mixed]
                    for (m, op), t in sorted(ops.items(),
                                             key=lambda kv: -kv[1])[:TOP]],
        "mixed_share": (sum(t for (_, op), t in ops.items() if op in mixed)
                        / total if total else None),
        "n_mixed_fusions": len(mixed),
        "spans_s": pt.span_self, "span_count": pt.span_count,
        "idle_split_s": pt.idle_split,
        "traced_call_s": _call_walls(path),
        "untraced_call_s": plain["window"]["commits"] / rate,
    }


def _print(t: dict) -> None:
    err = sys.stderr
    print(f"# {t['workload']} seed {t['seed']}: engine op time "
          f"{t['engine_op_s']:.4f} s over {t['traced_iters']} iterations",
          file=err)
    for s, secs in t["stages_s"].items():
        print(f"  {str(s):<24} {secs:10.4f} s "
              f"{t['stages_us_per_iter'][str(s)]:10.3f} us/iter", file=err)
    print(f"# mixed-stage fusions: {t['n_mixed_fusions']}, "
          f"{t['mixed_share']:.2%} of engine op time", file=err)
    for m, op, stage, secs, mix in t["top_ops"]:
        print(f"  {op:<24} {str(stage):<24} {secs:.4f} s"
              f"{'  (mixed)' if mix else ''}", file=err)
    for name, secs in sorted(t["spans_s"].items()):
        print(f"  span {name:<28} {secs:.4f} s x{t['span_count'][name]}",
              file=err)
    for name, secs in sorted(t["idle_split_s"].items()):
        print(f"  idle under {name:<28} {secs:.4f} s", file=err)
    print(f"# call wall: traced {t['traced_call_s']} s, untraced "
          f"{t['untraced_call_s']:.4f} s", file=err)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import run, spec
    if jax.devices()[0].platform != "tpu":
        print("stage_table: no TPU", file=sys.stderr)
        return 1
    run.enable_compile_cache()
    t = table(spec.load_cell(args.workload), args.seed)
    _print(t)
    print(json.dumps(t), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
