"""Benchmark aggregator: one module per paper figure/table.

Prints ``name,us_per_call,derived`` CSV. ``--full`` widens sweeps;
``--only fig08`` runs one module; ``--json PATH`` additionally writes the
parsed rows, per-module wall times, compile telemetry (jit-cache deltas,
XLA compile seconds, the slowest compiled functions), and per-module
sweep accounting (vmapped lane-iterations, compaction repack counts) as
machine-readable JSON so the perf trajectory is tracked across PRs —
the committed ``BENCH_run.json`` is the current quick-mode baseline, and
``benchmarks/perf_gate.py`` enforces it in CI.
"""
import argparse
import json
import sys
import time
import traceback


def _peak_rss_mb() -> float:
    """High-water-mark resident set of this process, in MiB.

    ``ru_maxrss`` is KiB on Linux, bytes on macOS; 0.0 where the
    ``resource`` module is unavailable (non-POSIX).
    """
    try:
        import resource
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        if sys.platform == "darwin":
            rss //= 1024
        return rss / 1024.0
    except Exception:
        return 0.0


def _parse_row(row: str) -> dict:
    """'name,us,k=v;k=v' -> record dict (values floated where clean).

    Tolerant: some modules (roofline_table) emit non-numeric columns; keep
    the raw string rather than failing the module's whole record set.
    """
    name, us, derived = (row.split(",", 2) + ["", ""])[:3]
    try:
        us = float(us)
    except ValueError:
        pass
    rec = {"name": name, "us_per_call": us}
    metrics = {}
    for kv in derived.split(";"):
        if "=" not in kv:
            continue
        k, v = kv.split("=", 1)
        try:
            metrics[k] = float(v)
        except ValueError:
            metrics[k] = v
    rec["derived"] = metrics
    return rec


def _top_fns(fns: dict, k: int = 5) -> dict:
    """Slowest-compiling functions from a telemetry delta (bounded)."""
    ranked = sorted(fns.items(), key=lambda kv: -kv[1]["secs"])
    return {name: rec for name, rec in ranked[:k]}


def merge_only_doc(doc: dict, path: str) -> tuple[dict, str | None]:
    """Merge a ``--only`` run's doc into the baseline JSON at ``path``.

    A single-module run refreshes that module's entry INSIDE the existing
    baseline instead of replacing the whole document — the CI smoke jobs
    each run ``--only figNN --json BENCH_run.json`` and must not wipe the
    other modules' perf trajectory. ``total_wall_s`` becomes the sum of
    module walls (the only consistent meaning for a merged doc).

    Returns ``(doc_to_write, note)``; ``note`` is non-None when the
    baseline was unusable (corrupt/foreign) — the caller prints it so the
    CI log says loudly that the trajectory was overwritten, not silently.
    A missing baseline is the normal fresh-file case: no note.
    """
    try:
        with open(path) as f:
            prev = json.load(f)
        prev["modules"].update(doc["modules"])
        prev["total_wall_s"] = sum(
            m.get("wall_s", 0.0) for m in prev["modules"].values())
        return prev, None
    except FileNotFoundError:
        return doc, None        # fresh file: write this run alone
    except (OSError, ValueError, KeyError, TypeError, AttributeError) as e:
        return doc, f"merge_skipped={type(e).__name__}: {e}"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable results JSON")
    args, _ = ap.parse_known_args()
    quick = not args.full

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (analysis_gate, common, compaction_bench,
                   fig02_motivation, fig06_ablation, fig07_mix,
                   fig08_scalability, fig09_sync, fig10_abort_skew,
                   fig12_tpcc, fig13_batch, fig14_recovery, fig15_adaptive,
                   fig16_brook, fig17_serving, fig18_waitprofile,
                   fig19_hotspot, kernel_bench, roofline_table)
    from repro.obs import compile_log
    compile_log.enable_telemetry()
    modules = {
        "fig02": fig02_motivation, "fig06": fig06_ablation,
        "fig07": fig07_mix, "fig08": fig08_scalability,
        "fig09": fig09_sync, "fig10": fig10_abort_skew,
        "fig12": fig12_tpcc, "fig13": fig13_batch,
        "fig14": fig14_recovery, "fig15": fig15_adaptive,
        "fig16": fig16_brook, "fig17": fig17_serving,
        "fig18": fig18_waitprofile, "fig19": fig19_hotspot,
        "compaction": compaction_bench,
        "kernels": kernel_bench, "roofline": roofline_table,
        "analysis": analysis_gate,
    }
    if args.only:
        modules = {args.only: modules[args.only]}
    # per-module compile counts depend on what ran before (cache entries
    # are created in run order) — the scope marker lets perf_gate.py
    # compare compile counts exactly only between like-scoped entries
    scope = f"only:{args.only}" if args.only else "suite"

    print("name,us_per_call,derived")
    doc = {"quick": quick, "modules": {}}
    t0 = time.time()
    for name, mod in modules.items():
        print(f"# --- {name} ---")
        sys.stdout.flush()
        tm = time.time()
        # compile accounting spans every jitted entry point (engine, aria,
        # traced runner, registered extras) — the sweep stats only see the
        # sweep substrate, so this is the whole-process truth per module
        tele0 = compile_log.snapshot()
        try:
            rows = mod.run(quick=quick) or []
        except Exception as e:  # keep going; the exit code reports it
            traceback.print_exc()
            print(f"{name}_ERROR,0,{type(e).__name__}:{e}")
            common.pop_sweep_stats()    # drop partial accounting
            tele = compile_log.delta(tele0)
            doc["modules"][name] = {
                "wall_s": time.time() - tm,
                "compiles": tele["compiles"],
                "compile_time_s": tele["compile_time_s"],
                "backend_compiles": tele["backend_compiles"],
                "peak_rss_mb": _peak_rss_mb(),
                "scope": scope,
                "error": f"{type(e).__name__}: {e}",
                "rows": [],
            }
            continue
        sweeps = common.pop_sweep_stats()
        tele = compile_log.delta(tele0)
        # per-module quick marker: a merged doc (--only into an existing
        # baseline, below) can mix modes, so the top-level flag alone
        # cannot be trusted for cross-commit comparisons
        doc["modules"][name] = {
            "wall_s": time.time() - tm,
            "quick": quick,
            "compiles": tele["compiles"],
            # wall seconds inside XLA backend compilation during this
            # module, and the slowest compiled functions it paid for —
            # the compile-time attack's per-module ledger
            "compile_time_s": tele["compile_time_s"],
            "backend_compiles": tele["backend_compiles"],
            "compiled_fns": _top_fns(tele["fns"]),
            # ru_maxrss is a process-lifetime high-water mark, so this is
            # monotone across modules in one run — compare same-position
            # or --only runs across commits, not adjacent modules
            "peak_rss_mb": _peak_rss_mb(),
            "scope": scope,
            "rows": [_parse_row(r) for r in rows],
            "sweeps": sweeps,
        }
        if sweeps:
            print(f"# {name}: {len(sweeps)} sweep(s), "
                  f"{sum(s['n_compiles'] for s in sweeps)} compile(s), "
                  f"{sum(s['lane_iters'] for s in sweeps)} lane-iters, "
                  f"{sum(s['n_repacks'] for s in sweeps)} repack(s), "
                  f"wall={doc['modules'][name]['wall_s']:.1f}s")
    doc["total_wall_s"] = time.time() - t0
    print(f"# total_wall_s={doc['total_wall_s']:.0f}")
    if args.json:
        out = doc
        if args.only:
            out, note = merge_only_doc(doc, args.json)
            if note:
                # corrupt/foreign baseline: overwriting loses the other
                # modules' trajectory — say so loudly in the output the
                # CI log keeps, rather than wiping it silently
                print(f"# {note}")
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
        print(f"# json_written={args.json}")
    errored = [n for n, m in doc["modules"].items() if "error" in m]
    if errored:
        print(f"# modules_errored={','.join(errored)}")
    return 1 if errored else 0


if __name__ == "__main__":
    sys.exit(main())
