"""The engine's promises, held on every protocol × workload kind.

``test_drain_quiesces_and_conserves`` runs each of the six tick-engine
protocols on each workload kind (``lock_matrix.SHAPES``) to quiescence
with injected aborts and checks what the engine promises at the end of
a drained run: every thread HALTs with no ticket left, every row's
applied counter equals its committed counter (no lost or dirty update,
cascades fully reverted), every thread-tick is charged to exactly one
breakdown bin, and work was done. ``test_aria_segmented_equals_single_shot``
holds Aria's segmented entry point to its docstring: batches are never
split, so a run cut into segments equals the single-shot run in every
leaf. No (protocol, kind) pair is rejected by the engine.
"""
import jax.numpy as jnp
import numpy as np

from lock_matrix import SHAPES, each_kind, each_pair
from repro.core.lock import (HALT, CostModel, EngineConfig, protocol_params,
                             run_sim)
from repro.core.lock import aria as A
from repro.obs.breakdown import check_conservation

HORIZON = 25_000


@each_pair
def test_drain_quiesces_and_conserves(proto, kind):
    wl, n_threads = SHAPES[kind]
    s = run_sim(EngineConfig(protocol=protocol_params(proto),
                             costs=CostModel(), workload=wl,
                             n_threads=n_threads, horizon=HORIZON,
                             p_abort=0.1, drain=True))
    assert bool((s.th.phase == HALT).all()), "threads failed to drain"
    assert bool((s.th.ticket < 0).all()), "ticket leak"
    np.testing.assert_array_equal(np.asarray(s.rows.applied_val),
                                  np.asarray(s.rows.committed_val))
    check_conservation(s, n_threads)
    assert int(s.g.commits) > 0


@each_kind
def test_aria_segmented_equals_single_shot(kind):
    wl, n_threads = SHAPES[kind]
    stat, dp = A.split_aria(A.AriaConfig(wl, CostModel(), n_threads,
                                         HORIZON))
    ref = A._run_dyn(stat, dp)
    seg, n_seg = A.init_aria_state(stat), 5
    for k in range(1, n_seg + 1):
        seg = A._run_seg_dyn(stat, dp, seg,
                             jnp.asarray(HORIZON * k // n_seg, jnp.int32))
    assert int(ref.commits) > 0
    for name, a, b in zip(ref._fields, ref, seg):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
