"""The protocol × workload-kind matrix that the engine tests run.

One small deployment per ``WorkloadSpec`` kind, so that each kind
compiles once per entry point: protocols, horizons, ``p_abort`` and
``drain`` are traced and add no compile. The hot-row kinds
(``hotspot_update``, ``fit``, ``tpcc``) run 40 threads, enough for a
queue to pass the 32-waiter promotion threshold, so that group locking
takes its own branches instead of matching O2; the spread-key kinds run
16 threads. ``zipf`` keeps the shape the adaptive tests use elsewhere.
"""
import pytest

from repro.core.lock import PROTOCOLS, WorkloadSpec

R = 256

# kind -> (workload, n_threads)
SHAPES = {
    "hotspot_update": (WorkloadSpec(kind="hotspot_update", txn_len=2,
                                    n_rows=R), 40),
    "hotspot_mix": (WorkloadSpec(kind="hotspot_mix", txn_len=2, n_rows=R,
                                 zipf_s=0.9, write_ratio=0.5), 16),
    "hotspot_scan": (WorkloadSpec(kind="hotspot_scan", txn_len=2, n_rows=R,
                                  n_hot=4), 16),
    "uniform": (WorkloadSpec(kind="uniform", txn_len=4, n_rows=R,
                             write_ratio=0.5), 16),
    "zipf": (WorkloadSpec(kind="zipf", txn_len=2, n_rows=R, zipf_s=0.9), 8),
    "fit": (WorkloadSpec(kind="fit", txn_len=2, n_rows=R, n_hot=2), 40),
    "tpcc": (WorkloadSpec(kind="tpcc", txn_len=4, n_rows=R), 40),
}
KINDS = tuple(SHAPES)

each_kind = pytest.mark.parametrize("kind", KINDS)
each_pair = pytest.mark.parametrize(
    "proto,kind", [(p, k) for p in PROTOCOLS for k in KINDS])
