"""Run one cell of BENCHMARK.json once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, the compile cache, warm-up of the cell's own shapes) is
timed as ``setup_s``. The window then runs the cell's driver for
``--seconds``; nothing compiles in it. With ``--trace 1`` the first call
of the window runs under the profiler, and the result carries the cell's
per-layer metrics instead of its end-to-end ones. After the window the
answers of every call are checked against the plain reference
(``bench/reference.py``) and the numbers are held to the cell's limits
(``bench/limits/<cell>.json``). The last line of standard output is one
JSON object (``window`` holds the driver's counts of the window, such as
calls and boundaries); the last lines of standard error list each number
compared beside its limit. The run exits non-zero, with no result line, when JAX
finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names another (JAX then reads it)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    return path


def device_info() -> dict:
    import jax
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def check(cell, outcome) -> tuple[bool, dict]:
    """Hold the worst call's numbers to the cell's limits."""
    from bench import calls, reference, spec
    wl = cell.config["workload"]
    cdf = (reference.zipf_cdf(wl["n_rows"], wl["zipf_s"])
           if wl["kind"] == "zipf" else None)
    per_call = [{**reference.numbers(wl, calls.to_host(kept), cdf), **host}
                for kept, host in zip(outcome.finals, outcome.numbers)]
    compare = getattr(spec.driver_module(cell.driver), "compare", None)
    if compare is not None:
        per_call += compare(cell, outcome)
    worst: dict = {}
    for nums in per_call:
        for k, v in nums.items():
            worst[k] = max(worst.get(k, v), v)
    checks = {}
    for name, limit in cell.limits["limits"].items():
        checks[name] = {"value": worst.get(name), "limit": limit}
    correct = bool(checks) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    return correct, checks


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float = T_START, trace_dir: str = TRACE_DIR) -> dict:
    """The result line of one run of ``cell`` (a ``bench.spec.Cell``)."""
    from bench import calls, spec, tracing
    tracer = tracing.Tracer(trace, trace_dir)
    ctx = calls.Context(cell=cell, seed=seed, seconds=seconds,
                        tracer=tracer, t_start=t_start)
    driver = spec.driver_module(cell.driver)
    outcome = driver.run(ctx)
    device = device_info()
    correct, checks = check(cell, outcome)
    result = {"correct": correct, "attempted": outcome.attempted,
              "failed": outcome.failed}
    if trace:
        summary = tracing.reduce(tracer.xplane())
        obs = {"trace": summary, "counters": outcome.counters}
        metrics = {}
        for m in cell.per_layer:
            v = spec.layer_module(m["name"]).read(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary.mean_busy_s, window_s=summary.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    else:
        values = {**outcome.e2e, "setup_s": ctx.setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["window"] = outcome.counters
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: no program under {SRC}", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, SRC]
    from bench import spec
    cell = spec.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s), JAX "
              f"sees {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
