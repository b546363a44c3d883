"""Profiler quickstart: which engine stage does each compiled op belong to?

    PYTHONPATH=src python examples/profile_quickstart.py

What this demonstrates (DESIGN.md §12.1):

1. ``stage_map`` — every instruction of a compiled engine program carries
   one of the ``jax.named_scope`` labels in ``engine.STAGE_SCOPES`` in its
   HLO metadata. ``repro.obs.prof.stage_map`` reads them back, so the
   per-operation device times of a profiler trace (which name an op by
   its HLO instruction alone) add up per stage. Printed here: how many
   instructions each stage holds in ``_run_seg_dyn`` on a small config,
   and how many fusions mix stages (the limit of the attribution).
2. Compile telemetry — ``obs.compile_log`` counts the XLA backend wall
   these executables cost, attributed per function name.

Device time per stage needs the chip: ``python3 bench/stage_table.py
--workload <cell> --seed <n>`` on a TPU prints it for a benchmark cell.
"""
import collections
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp  # noqa: E402

from repro.core.lock import (CostModel, EngineConfig, WorkloadSpec,  # noqa
                             engine, protocol_params)
from repro.obs import compile_log, mixed_fusions, stage_map  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

WL = WorkloadSpec(kind="zipf", txn_len=4, n_rows=4096)


def main():
    tele0 = compile_log.snapshot()
    cfg = EngineConfig(protocol=protocol_params("mysql"), costs=CostModel(),
                       workload=WL, n_threads=64, horizon=200_000)
    stat, dp = engine.split_config(cfg)
    args = (stat, dp, engine.init_state_dyn(stat, dp),
            jnp.asarray(10_000, jnp.int32))
    stages = stage_map(engine._run_seg_dyn, *args)
    counts = collections.Counter(stages.values())
    print(f"_run_seg_dyn mysql T=64 L=4 R=4096: {len(stages)} HLO "
          f"instructions")
    for name in engine.STAGE_SCOPES + (None,):
        label = name or "(none: parameters, constants, XLA copies)"
        print(f"  {label:<46}{counts.get(name, 0):>6}")
    assert set(counts) - {None} <= set(engine.STAGE_SCOPES)
    assert counts["seg_snapshot"] > 0 and counts["stage_derive"] > 0
    text = engine._run_seg_dyn.lower(*args).compile().as_text()
    print(f"fusions that mix stages: {len(mixed_fusions(text))}")
    tele = compile_log.delta(tele0)
    slow = sorted(tele["fns"].items(), key=lambda kv: -kv[1]["secs"])[:3]
    print(f"compile telemetry: {tele['compile_time_s']:.1f}s XLA wall over "
          f"{tele['backend_compiles']} backend compiles; slowest: "
          + ", ".join(f"{n} {r['secs']:.1f}s" for n, r in slow))


if __name__ == "__main__":
    enable_compile_cache()
    main()
