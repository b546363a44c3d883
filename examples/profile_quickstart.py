"""Profiler quickstart: where does an engine iteration's wall time go?

    PYTHONPATH=src python examples/profile_quickstart.py

What this demonstrates (DESIGN.md §12):

1. ``profile_step`` — the stage-ablation step profiler. The engine step
   is one fused XLA program inside a ``lax.while_loop``; no span-based
   profiler can see inside it, so each stage (dup analysis, deadlock
   walk, ticket grant, commit-cursor derivation, group/hotspot branches,
   tick charging) is instead *ablated* — replaced by a stand-in XLA
   dead-code-eliminates — and the steady-state per-iteration wall of the
   ablated executable is differenced against the full step on the same
   warmed ``SimState``. One executable per ablation, compile counts
   asserted, and the stand-ins are bit-exact no-ops under designated
   configs (tests/test_prof.py), so the difference is the stage's cost.
2. The ranked table — ``commit_cursor`` (the T*L -> R segment
   reductions in ``_derive``) dominates on the paper's hotspot shape:
   that scan is the fusion target for the ROADMAP's "Pallas-kernel the
   engine hot path" item, and the profiler is how we'll know the kernel
   actually moved it.
3. Compile telemetry — ``obs.compile_log`` counts the XLA backend wall
   these executables cost, attributed per function name.
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.lock import (CostModel, EngineConfig, WorkloadSpec,
                             protocol_params)
from repro.obs import compile_log, profile_step, rank_table
from repro.compile_cache import enable_compile_cache

WL = WorkloadSpec(kind="hotspot_update", txn_len=4, n_rows=512)


def main():
    tele0 = compile_log.snapshot()
    for proto in ("mysql", "brook2pl"):
        cfg = EngineConfig(protocol=protocol_params(proto),
                           costs=CostModel(), workload=WL,
                           n_threads=64, horizon=2_000_000)
        prof = profile_step(cfg, n_iters=64, repeats=2)
        print(rank_table(prof))
        assert abs(sum(s.fraction for s in prof.stages) - 1.0) < 1e-9
        assert prof.compiles == len(prof.stages)   # stages + other - full
        print()
    tele = compile_log.delta(tele0)
    slow = sorted(tele["fns"].items(), key=lambda kv: -kv[1]["secs"])[:3]
    print(f"compile telemetry: {tele['compile_time_s']:.1f}s XLA wall over "
          f"{tele['backend_compiles']} backend compiles; slowest: "
          + ", ".join(f"{n} {r['secs']:.1f}s" for n, r in slow))


if __name__ == "__main__":
    enable_compile_cache()
    main()
