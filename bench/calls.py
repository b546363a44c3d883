"""What the drivers share: per-call seeds, the run context, the outcome."""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.reference import CallFinal
from bench.spec import Cell
from bench.tracing import Tracer

SEED_MASK = 0x7FFFFFFF      # WorkloadSpec.seed is an int32 on the device
# the engine's jitted entry points (repro.core.lock.engine)
ENGINE_ENTRIES = ("_run_dyn", "_run_seg_dyn", "_run_batch", "_run_seg_batch")


def call_seed(seed: int, k: int) -> int:
    """The workload seed of call ``k`` of a run seeded ``seed`` (k = -1 is
    the warm-up call). Any non-negative ``seed`` is taken whole."""
    ss = np.random.SeedSequence([int(seed), k + 1])
    return int(ss.generate_state(1)[0]) & SEED_MASK


@dataclasses.dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    tracer: Tracer
    t_start: float                      # perf_counter at process start
    setup_end: float | None = None

    def setup_done(self) -> None:
        self.setup_end = time.perf_counter()

    @property
    def setup_s(self) -> float:
        return self.setup_end - self.t_start


@dataclasses.dataclass
class Outcome:
    """What a driver's window produced; checked after the window closed."""
    e2e: dict                   # end-to-end metric name -> value
    attempted: int
    failed: int
    finals: list                # device-side final leaves, one per call
    numbers: list               # host-side check numbers, one dict per call
    counters: dict              # inputs of the per-layer readers
    # what the driver's ``compare`` holds to a reference after the window
    answers: list = dataclasses.field(default_factory=list)


def keep_final(state, seed: int, hot_base: int) -> tuple:
    """The leaves of a final ``SimState`` that the check reads (on device;
    the rest of the state can be freed)."""
    th, rows, g = state.th, state.rows, state.g
    return (seed, hot_base, th.txn, th.ticket, th.applied, rows.applied_val,
            rows.committed_val, g.tb, g.now, g.commits, g.user_aborts,
            g.iters)


def to_host(kept: tuple) -> CallFinal:
    import jax
    seed, hot_base, *leaves = kept
    (txn, ticket, applied, applied_val, committed_val, tb, now, commits,
     user_aborts, iters) = jax.device_get(leaves)
    return CallFinal(
        seed=seed, hot_base=hot_base, t_pad=int(txn.shape[0]),
        txn=np.asarray(txn), ticket=np.asarray(ticket),
        applied=np.asarray(applied), applied_val=np.asarray(applied_val),
        committed_val=np.asarray(committed_val), tb=np.asarray(tb),
        now=int(now), commits=int(commits), user_aborts=int(user_aborts),
        iters=int(iters))


def workload_spec(cfg: dict, seed: int):
    """The program's WorkloadSpec of a configuration, for one call."""
    from repro.core.lock import WorkloadSpec
    wl = cfg["workload"]
    hot_base = seed % wl["n_rows"] if cfg.get("seeded_hot_row") else 0
    return WorkloadSpec(kind=wl["kind"], n_rows=wl["n_rows"],
                        txn_len=wl["txn_len"],
                        write_ratio=wl.get("write_ratio", 1.0),
                        zipf_s=wl.get("zipf_s", 0.7), seed=seed,
                        hot_base=hot_base)


def costs(cfg: dict):
    from repro.core.lock import CostModel
    return CostModel(**cfg["costs"])


def window_compiles(before: dict) -> int:
    """Programs compiled or loaded since ``before`` (a compile_log snapshot):
    new jit-cache entries, or backend compiles, whichever is more."""
    from repro.obs import compile_log
    d = compile_log.delta(before)
    return max(int(d["compiles"]), int(d["backend_compiles"]))
