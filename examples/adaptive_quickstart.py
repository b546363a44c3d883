"""Adaptive governor quickstart: drift schedule -> policy -> run -> summary.

Successor of the old hotspot_cc_demo, rewired onto the governor API
(``repro.adaptive``). The happy path is three lines::

    drift = skew_ramp(WorkloadSpec(kind="zipf", txn_len=4, n_rows=4096), 8)
    res = run_governed([GovernorCell("adaptive", QueueRulePolicy(), drift,
                                     n_threads=64, costs=CM)],
                       horizon=120_000, n_segments=8)
    print(summarize(res))

    PYTHONPATH=src python examples/adaptive_quickstart.py
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.adaptive import (EpsilonGreedyPolicy, FixedPolicy, GovernorCell,
                            QueueRulePolicy, preset_timeline, run_governed)
from repro.core.lock import CostModel, WorkloadSpec, skew_ramp
from repro.sweep import save_results, summarize
from repro.compile_cache import enable_compile_cache

CM = CostModel(op_exec=20, commit_base=30)   # lock-manager-bound OLTP


def main():
    # 1. build a drift schedule: Zipf skew ramps across the run, crossing
    #    the deadlock valley where detection-free protocols stall
    base = WorkloadSpec(kind="zipf", txn_len=4, n_rows=4096)
    drift = skew_ramp(base, 8, lo=0.3, hi=0.7)

    # 2. pick policies: the paper's queue rule, a greedy searcher, and
    #    fixed-protocol baselines riding the same segmented substrate
    cells = [
        GovernorCell("adaptive_rule", QueueRulePolicy(), drift, 64,
                     costs=CM),
        GovernorCell("adaptive_greedy", EpsilonGreedyPolicy(), drift, 64,
                     costs=CM),
        GovernorCell("fixed_mysql", FixedPolicy("mysql"), drift, 64,
                     costs=CM),
        GovernorCell("fixed_o2", FixedPolicy("o2"), drift, 64, costs=CM),
    ]

    # 3. run governed (one engine compile for all cells and segments)
    res = run_governed(cells, horizon=120_000, n_segments=8)

    print("name,us_per_call,derived")
    for row in summarize(res):
        print(row)
    print(f"# {len(cells)} cells x 8 segments, "
          f"{res.n_compiles} engine compile(s)")
    for name in ("adaptive_rule", "adaptive_greedy"):
        print(f"# {name} timeline: {' -> '.join(preset_timeline(res, name))}")

    out = os.environ.get("ADAPTIVE_QUICKSTART_JSON",
                         "/tmp/adaptive_quickstart.json")
    save_results(out, res, meta={"example": "adaptive_quickstart"})
    print(f"# per-segment records written to {out} (repro.sweep/v3)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
