"""Device time of the engine programs per loop iteration outside the five
stage groups above, in us: ``engine_us_per_iter`` less them. It holds the
other scopes of ``engine.STAGE_SCOPES`` (aborts, grant and commit
bookkeeping, time advance, completions, loop control), XLA's own copies,
ops in no scope, and the gaps inside the modules. The six ``stage_*``
metrics sum to ``engine_us_per_iter``."""

from bench import program_trace
from bench.layers import (engine_us_per_iter, stage_derive_us_per_iter,
                          stage_gen_txn_us_per_iter, stage_grant_us_per_iter,
                          stage_group_us_per_iter,
                          stage_tick_charge_us_per_iter)

GROUPS = (stage_gen_txn_us_per_iter, stage_derive_us_per_iter,
          stage_grant_us_per_iter, stage_group_us_per_iter,
          stage_tick_charge_us_per_iter)


def read(obs):
    total = engine_us_per_iter.read(obs)
    named = program_trace.stage_us_per_iter(
        obs, "stage_unscoped_us_per_iter",
        [s for g in GROUPS for s in g.SCOPES])
    if total is None or named is None:
        return None
    return total - named
