"""Observability layer for the lock engine (opt-in, traced-flag gated).

Three parts (DESIGN.md §11):

* **Tick attribution** — the engine itself charges every thread-tick to a
  ``TickBreakdown`` bin (``Globals.tb``; exec / lock_wait / commit_wait /
  rollback / detection / sync / idle, split cold/hot); this package holds
  the conservation check (``sum(tb) == T * elapsed``) and report helpers.
* **Event tracing** (:mod:`.trace`) — a fixed-allocation on-device buffer
  capturing {tick, thread, row, event} inside the ``lax.while_loop``;
  capacity and the on-switch are traced data, so tracing never recompiles
  and ``trace_on=False`` is bit-exact with the untraced engine.
* **Export** (:mod:`.export`) — Chrome trace-event JSON (Perfetto) and
  text wait-profile / breakdown reports.

:mod:`.compile_log` is the shared compile counter + compile-time
telemetry benchmarks use to put recompile regressions on the perf
trajectory; :mod:`.prof` maps the instructions of a compiled engine
program to the engine's stage scopes (DESIGN.md §12.1), which turns a
device trace's per-operation time into per-stage time.

**Hotspot attribution** (DESIGN.md §14): :mod:`.hotspot` ranks the
engine's per-record contention accumulator (``Globals.ca``, gated by
``EngineConfig.attrib``) into wait-share/Gini/threshold-rule reports and
asserts its conservation against the TickBreakdown; :mod:`.blame` pairs
TraceBuf wait spans with the holding transaction attempts into a blame
matrix, per-record blame table, and the longest blocking chain.
"""
from . import blame, breakdown, compile_log, export, hotspot, prof, trace
from .breakdown import (breakdown_row, check_conservation, fractions,
                        tick_sum)
from .prof import STAGE_NOOPS, mixed_fusions, op_stages, stage_map
from .export import (breakdown_table, dump_chrome_trace, to_chrome_trace,
                     wait_profile)
from .blame import (BlameResult, blame_matrix, blame_table, critical_path)
from .hotspot import (check_ca_conservation, gini, hotspot_lane_events,
                      hotspot_report, hotspot_summary, top_share,
                      wait_share)
from .trace import (EVENTS, EV_ABORT, EV_COMMIT, EV_GRANT, EV_GROUP_JOIN,
                    EV_RELEASE, EV_TIMEOUT, EV_VICTIM, EV_WAIT_ENTER,
                    TraceBuf, events_host, make_trace, run_traced,
                    simulate_traced)

__all__ = [
    "blame", "breakdown", "compile_log", "export", "hotspot", "prof",
    "trace",
    "breakdown_row", "check_conservation", "fractions", "tick_sum",
    "STAGE_NOOPS", "mixed_fusions", "op_stages", "stage_map",
    "breakdown_table", "dump_chrome_trace", "to_chrome_trace",
    "wait_profile",
    "BlameResult", "blame_matrix", "blame_table", "critical_path",
    "check_ca_conservation", "gini", "hotspot_lane_events",
    "hotspot_report", "hotspot_summary", "top_share", "wait_share",
    "EVENTS", "EV_ABORT", "EV_COMMIT", "EV_GRANT", "EV_GROUP_JOIN",
    "EV_RELEASE", "EV_TIMEOUT", "EV_VICTIM", "EV_WAIT_ENTER",
    "TraceBuf", "events_host",
    "make_trace", "run_traced", "simulate_traced",
]
