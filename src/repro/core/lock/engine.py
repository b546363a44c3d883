"""Vectorized discrete-time concurrency-control engine (the paper's core).

The engine simulates T database worker threads executing transactions over R
rows under one of six locking protocols (MySQL-2PL, O1 lightweight, O2
queue locking, TXSQL group locking, Bamboo, Brook-2PL), tick-accurately,
entirely as a compiled JAX program (``lax.while_loop`` over simulated time;
all state in arrays). Aria lives in ``aria.py`` (its batch structure needs
no tick loop). Brook-2PL ("Tolerating High Contention Workloads with A
Deadlock-Free Two-Phase Locking Protocol", Habibi et al., arXiv:2508.18576)
is the statically-analysed member: ``chop.py`` derives a canonical
lock-acquisition order and per-op release points from the workload's
transaction templates, and the engine consumes them through two masked
protocol branches — ``ordered_acquire`` (tickets taken in canonical row
order, making waits-for cycles structurally impossible: no detection walk,
no timeouts, no deadlock rollbacks) and ``per_op_release`` (a ticket
retires from the commit-order dependency at its key's last-use op, with
the ``cc``/``top`` cascade machinery still guarding dirty reads).

Modeling choices (see DESIGN.md §2.1):

* Every row's lock wait queue is a **ticket queue**: ``nt[r]`` is the next
  ticket; a thread takes a ticket when it reaches a write op. Queue/grant
  order is ticket order (FIFO, as in lock_sys / hot_row_hash).
* The grant rule is the protocol: strict-2PL rows grant ticket k when every
  ticket < k has *committed* (released); early-release rows (group-locking
  hot rows; every row under Bamboo) grant when every ticket < k has
  *applied its update* (Fig. 3).
* Rather than maintaining mutable queues, per-row aggregates (``us`` = next
  grantable ticket, ``cc`` = lowest uncommitted applied ticket = commit
  cursor, ``top`` = highest applied ticket, holder, queue length) are
  **re-derived every iteration from the per-thread ticket table** with
  segment reductions. Aborts simply clear ticket slots; order invariants
  are restored declaratively, which makes cascades and timeouts robust.
* The dependency list of the paper is exactly the ticket order of applied
  updates: commit requires ``cc[row] == my_ticket`` (commit order = update
  order, Alg. 2); cascades roll back from ``top`` downward (Alg. 3).
* Costs are integer ticks (0.1us); see ``costs.py`` for where each cost
  lands and why (deadlock-detection on the grant path reproduces Fig. 2a).

The per-row value is modeled as a counter: every applied write is +1 and
every rollback is -1, so serializability is *checkable*: at quiescence the
counter must equal the number of committed writes (no lost updates, no
dirty leftovers) — see tests/test_lock_properties.py.

Batching (DESIGN.md §3): every protocol flag, cost constant, and workload
parameter is a **traced jnp scalar** carried in :class:`DynParams`; the only
static compile keys are the array shapes (T, L, R) and the workload kind
(:class:`StaticShape`). Protocol branches are computed unconditionally and
selected with masks, so one compiled program serves *every* protocol /
timeout / abort-rate / skew combination at a given shape — and the sweep
subsystem (``repro.sweep``) can stack G configs and run them under
``jax.vmap`` as one program. ``simulate()`` routes through the very same
dynamic step, which makes vmapped-lane results bit-identical to per-config
runs by construction. Threads and op slots are padded to the grid max:
padded threads start in HALT and never act; padded slots never execute
(``nops`` stops the op cursor first), so padding is bitwise invisible.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.profiler import TraceAnnotation

from .costs import CostModel, ProtocolParams, protocol_params
from .workload import (WorkloadSpec, DynWorkload, dyn_workload, gen_txn_dyn,
                       will_abort_dyn)

I32 = jnp.int32
F32 = jnp.float32
INF = jnp.int32(2**30)
NOTK = jnp.int32(-1)          # "no ticket"
N_HIST = 64
HIST_BASE = 1.3

# thread phases
START, WAIT, EXEC, CWAIT, COMMIT, RBACK, RBWAIT, BACKOFF, ARRIVE, HALT = \
    range(10)

# --- tick attribution (obs layer, DESIGN.md §11) -------------------------
# Every thread-tick of the horizon lands in exactly one TickBreakdown bin,
# split by protocol branch (cold = plain 2PL path, hot = the thread's
# current row is promoted hot), so sum(Globals.tb) == T * Globals.now is a
# hard conservation invariant (asserted in tests; i32, exact mod 2^32).
N_TB = 7
TB_EXEC, TB_LOCKWAIT, TB_COMMITWAIT, TB_ROLLBACK, TB_DETECT, TB_SYNC, \
    TB_IDLE = range(N_TB)
TB_NAMES = ("exec", "lock_wait", "commit_wait", "rollback", "detection",
            "sync", "idle")
TB_BRANCHES = ("cold", "hot")
# phase -> bin. START/ARRIVE/HALT are idle (no txn holds the thread);
# RBACK work + RBWAIT turn-waits + BACKOFF all charge the rollback bin;
# COMMIT work (commit_base + sync window) charges sync. EXEC splits at
# runtime: the deadlock-detection ticks folded into the grant overhead
# (Threads.detleft) are consumed first and charged to TB_DETECT.
_TB_PHASE_BIN = np.array(
    [TB_IDLE, TB_LOCKWAIT, TB_EXEC, TB_COMMITWAIT, TB_SYNC,
     TB_ROLLBACK, TB_ROLLBACK, TB_ROLLBACK, TB_IDLE, TB_IDLE],
    dtype=np.int32)

# log2 buckets for snapshot occupancy histograms: bucket 0 = empty,
# bucket b>=1 = count in [2**(b-1), 2**b). 12 buckets cover queues of 2k+.
N_QHIST = 12

# --- per-record contention attribution (obs layer, DESIGN.md §14) --------
# ``Globals.ca`` is an (N_CA, R) i32 accumulator scattered per ROW at the
# tick-charge site, the per-record twin of the per-phase TickBreakdown.
# CA_WAIT charges dt at the thread's current-op row under exactly the
# TB_LOCKWAIT mask, so ``ca[CA_WAIT].sum() == tb[:, TB_LOCKWAIT].sum()``
# (cold+hot) is a hard conservation invariant, asserted per run and per
# governed segment. Gated by the traced ``DynParams.attrib`` flag: the
# accumulator is write-only, so attribution-off runs are bit-exact with
# the pre-accumulator engine in every other leaf, with zero extra
# compiles. i32 like tb: exact mod 2^32.
N_CA = 6
CA_WAIT, CA_GRANTS, CA_TIMEOUTS, CA_VICTIMS, CA_QSUM, CA_QMAX = range(N_CA)
CA_NAMES = ("wait_ticks", "grants", "timeouts", "victims",
            "queue_sum", "queue_max")

# --- stage scopes (DESIGN.md §12.1) --------------------------------------
# Every instruction of an engine program carries one of these
# ``jax.named_scope`` labels in its HLO ``op_name`` metadata, so device time
# read off a trace (keyed by instruction name) attributes to stages through
# ``repro.obs.prof.op_stages``. The step's scopes do not nest;
# ``seg_snapshot`` wraps a ``_derive`` call, and the outermost scope wins.
# Labels are metadata only: they add no operation and change no result.
STAGE_SCOPES = (
    # the loop body, in step order
    "stage_derive",          # _derive: T*L -> R segment reductions
    "stage_mark_aborts",     # 1a/1c/1d timeouts, proactive, cascades; 2
    "stage_deadlock_walk",   # 1b waits-for cycle walk
    "stage_grant_apply",     # 4a grant costs, counters, updating flags
    "stage_ticket_grant",    # 4a grant-rule mask
    "stage_group_lock",      # 4a group leader/member bookkeeping
    "stage_commit_order",    # 4b commit eligibility and phase moves
    "stage_group_commit",    # 4b group-commit sync windows
    "stage_rollback_turn",   # 4c RBWAIT -> RBACK
    "stage_advance",         # 5 dt, time and work advance
    "stage_tick_charge",     # 5 TickBreakdown and per-record scatters
    "stage_complete",        # 6 completions: apply, commit, rollback
    "stage_txn_start",       # 7 halts, arrival gate, new-txn selection
    "stage_gen_txn",         # 7 gen_txn_dyn key generation
    "stage_op_begin",        # 8 direct exec, tickets, next-ticket counts
    "stage_ticket_assign",   # 8 FIFO same-tick ranking (argsort)
    "stage_hotspot_detect",  # 9 hot-row promotion / demotion
    # loop control, outside the body
    "loop_setup",            # loop-invariant values hoisted before it
    "loop_cond",             # the while condition
    # after the loop (segmented entry points)
    "seg_snapshot",          # boundary telemetry (SegSnapshot)
)

# --- stage ablation (control seam, DESIGN.md §12) ------------------------
# ``_make_step_events(..., ablate={stage})`` replaces one named stage's
# compute with a shape-correct stand-in so XLA dead-code-eliminates the
# stage from the compiled program; the benchmark's control
# (``bench/control.py``) builds the program with a stage switched off
# through it. Each ablation is the exact identity on the step
# whenever the stage's work is trivially absent (protocol flag off,
# read-only workload, txn_len 1 — asserted bit-exactly in
# tests/test_prof.py), and ``ablate=frozenset()`` (every production entry
# point) emits the identical program as before the seam existed.
PROF_STAGES = (
    "dup_analysis",    # gen_txn_dyn's (T,L,L) pairwise dup/last-use scan
    "deadlock_walk",   # the 8-hop waits-for cycle walk (stage 1b)
    "ticket_grant",    # grant-rule masks (4a) + FIFO ticket argsort (8)
    "commit_cursor",   # _derive: cc/top/us/holder T*L -> R seg reductions
    "group_hotspot",   # group-lock / group-commit / hotspot-detect conds
    "tick_charge",     # TickBreakdown scatter charging (stage 5)
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    protocol: ProtocolParams
    costs: CostModel
    workload: WorkloadSpec
    n_threads: int = 64
    horizon: int = 2_000_000          # ticks (0.1us) => 0.2s simulated
    p_abort: float = 0.0              # injected commit-time aborts (Fig 10)
    drain: bool = False               # run until all threads quiesce
    max_iters: int = 1_500_000
    seed: int = 0
    attrib: bool = False              # per-record contention accumulator


class StaticShape(NamedTuple):
    """The compile key: everything that picks the program, nothing else."""
    kind: str           # workload kind
    n_threads: int      # padded thread count T
    txn_len: int        # padded op-slot count L
    n_rows: int         # key space R


class DynParams(NamedTuple):
    """Traced per-config parameters (one vmap lane each in a sweep).

    Protocol flags are jnp bools, costs jnp ints/floats; semantics match
    ``ProtocolParams`` / ``CostModel`` / ``EngineConfig`` field-for-field.
    ``n_active`` masks padded threads (tid >= n_active start in HALT).
    """
    # --- protocol ---
    lock_base: jnp.ndarray
    grant_cost: jnp.ndarray
    dd_coeff: jnp.ndarray
    has_detection: jnp.ndarray
    hot_queue: jnp.ndarray
    early_release: jnp.ndarray
    early_all: jnp.ndarray
    group_lock: jnp.ndarray
    group_commit: jnp.ndarray
    dynamic_batch: jnp.ndarray
    batch_size: jnp.ndarray
    hot_threshold: jnp.ndarray
    proactive_abort: jnp.ndarray
    ordered_acquire: jnp.ndarray
    per_op_release: jnp.ndarray
    wait_timeout: jnp.ndarray
    commit_wait_timeout: jnp.ndarray
    # --- costs ---
    op_exec: jnp.ndarray
    read_exec: jnp.ndarray
    commit_base: jnp.ndarray
    sync_lat: jnp.ndarray
    rb_base: jnp.ndarray
    rb_per_op: jnp.ndarray
    backoff: jnp.ndarray
    arrival_rate: jnp.ndarray
    rb_turn_timeout: jnp.ndarray
    # --- run ---
    horizon: jnp.ndarray
    p_abort: jnp.ndarray
    drain: jnp.ndarray
    max_iters: jnp.ndarray
    n_active: jnp.ndarray
    # (T,) per-thread transaction quota: a thread reaching START with
    # ``txn >= txn_cap[tid]`` HALTs instead of generating a new txn. INF
    # (the split_config default) is the closed loop — the check is then
    # identically false, so classic runs are bitwise unchanged. The
    # serving layer (repro.serving) meters this as admission credits and
    # revives HALTed slots between segments, which turns thread slots
    # into an open-system worker pool.
    txn_cap: jnp.ndarray
    # Per-record contention attribution on/off (Globals.ca). Traced like
    # every other knob — flipping it reuses the compiled program; the
    # accumulator is write-only so the off branch leaves every other
    # state leaf bit-exact.
    attrib: jnp.ndarray
    # --- workload ---
    wl: DynWorkload


def split_config(cfg: EngineConfig, pad_threads: int | None = None,
                 pad_len: int | None = None) -> tuple[StaticShape, DynParams]:
    """EngineConfig -> (compile key, traced params). Eager — not for jit."""
    p, c, w = cfg.protocol, cfg.costs, cfg.workload
    T = pad_threads or cfg.n_threads
    L = pad_len or w.txn_len
    assert T >= cfg.n_threads and L >= w.txn_len
    stat = StaticShape(kind=w.kind, n_threads=T, txn_len=L, n_rows=w.n_rows)
    i32 = lambda v: jnp.asarray(v, I32)
    f32 = lambda v: jnp.asarray(v, F32)
    b = lambda v: jnp.asarray(v, bool)
    dp = DynParams(
        lock_base=i32(p.lock_base), grant_cost=i32(p.grant_cost),
        dd_coeff=f32(p.dd_coeff), has_detection=b(p.has_detection),
        hot_queue=b(p.hot_queue), early_release=b(p.early_release),
        early_all=b(p.early_all), group_lock=b(p.group_lock),
        group_commit=b(p.group_commit), dynamic_batch=b(p.dynamic_batch),
        batch_size=i32(p.batch_size), hot_threshold=i32(p.hot_threshold),
        proactive_abort=b(p.proactive_abort),
        ordered_acquire=b(p.ordered_acquire),
        per_op_release=b(p.per_op_release),
        wait_timeout=i32(p.wait_timeout),
        commit_wait_timeout=i32(p.commit_wait_timeout),
        op_exec=i32(c.op_exec), read_exec=i32(c.read_exec),
        commit_base=i32(c.commit_base), sync_lat=i32(c.sync_lat),
        rb_base=i32(c.rb_base), rb_per_op=i32(c.rb_per_op),
        backoff=i32(c.backoff), arrival_rate=f32(c.arrival_rate),
        rb_turn_timeout=i32(c.rb_turn_timeout),
        horizon=i32(cfg.horizon), p_abort=f32(cfg.p_abort),
        drain=b(cfg.drain), max_iters=i32(cfg.max_iters),
        n_active=i32(cfg.n_threads),
        txn_cap=jnp.full((T,), INF, I32),
        attrib=b(cfg.attrib),
        wl=dyn_workload(w),
    )
    return stat, dp


class Threads(NamedTuple):
    phase: jnp.ndarray      # (T,)
    work: jnp.ndarray       # (T,) remaining ticks in paying phase
    op: jnp.ndarray         # (T,) current op slot
    txn: jnp.ndarray        # (T,) txn counter
    tstart: jnp.ndarray     # (T,) first-attempt start tick
    wstart: jnp.ndarray     # (T,) wait start tick
    willab: jnp.ndarray     # (T,) bool: injected abort at commit
    forced: jnp.ndarray     # (T,) bool: forced abort pending
    vabort: jnp.ndarray     # (T,) bool: abort is voluntary (move to next txn)
    retry: jnp.ndarray      # (T,) bool: current txn is a retry
    keys: jnp.ndarray       # (T, L)
    iswr: jnp.ndarray       # (T, L) bool
    dup: jnp.ndarray        # (T, L) bool
    ticket: jnp.ndarray     # (T, L) ticket or -1
    applied: jnp.ndarray    # (T, L) bool
    early: jnp.ndarray      # (T, L) bool: early-release semantics at apply
    committing: jnp.ndarray  # (T, L) bool: entered the commit queue
    lastu: jnp.ndarray      # (T, L) bool: slot is its key's last use (chop)
    released: jnp.ndarray   # (T, L) bool: ticket retired at its release pt
    nops: jnp.ndarray       # (T,)
    detleft: jnp.ndarray    # (T,) detection ticks left in current EXEC work


class Rows(NamedTuple):
    nt: jnp.ndarray         # (R,) next ticket
    updating: jnp.ndarray   # (R,) bool: an update is executing
    hot: jnp.ndarray        # (R,) bool
    gleader: jnp.ndarray    # (R,) leader ticket of OPEN group, -1 if closed
    gcount: jnp.ndarray     # (R,) members granted in open group
    casc: jnp.ndarray       # (R,) cascade low ticket (INF = none)
    batch_end: jnp.ndarray  # (R,) group-commit batch completion tick
    batch_n: jnp.ndarray    # (R,) members in the open commit batch
    applied_val: jnp.ndarray    # (R,) net applied increments
    committed_val: jnp.ndarray  # (R,) committed increments


class Globals(NamedTuple):
    now: jnp.ndarray
    commits: jnp.ndarray
    user_aborts: jnp.ndarray
    forced_aborts: jnp.ndarray
    lock_ops: jnp.ndarray
    wait_ticks: jnp.ndarray     # f32 (lock-wait thread-ticks)
    busy_ticks: jnp.ndarray     # f32 (executing/committing thread-ticks)
    lat_sum: jnp.ndarray        # f32
    hist: jnp.ndarray           # (N_HIST,) i32 latency histogram
    dd_ticks: jnp.ndarray       # deadlock-detection ticks paid on grants
    iters: jnp.ndarray
    tb: jnp.ndarray             # (len(TB_BRANCHES), N_TB) i32 TickBreakdown
    ca: jnp.ndarray             # (N_CA, R) i32 per-record contention


class SimState(NamedTuple):
    th: Threads
    rows: Rows
    g: Globals


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _seg_min(data, segs, R, valid):
    data = jnp.where(valid, data, INF)
    return jax.ops.segment_min(data.reshape(-1), segs.reshape(-1),
                               num_segments=R)


def _seg_max(data, segs, R, valid):
    data = jnp.where(valid, data, -1)
    return jax.ops.segment_max(data.reshape(-1), segs.reshape(-1),
                               num_segments=R)


def _seg_sum(data, segs, R, valid):
    data = jnp.where(valid, data, 0)
    return jax.ops.segment_sum(data.reshape(-1), segs.reshape(-1),
                               num_segments=R)


def _hist_bucket(lat):
    b = jnp.log(lat.astype(F32) + 1.0) / np.log(HIST_BASE)
    return jnp.clip(b.astype(I32), 0, N_HIST - 1)


def _stop_time(dp: DynParams):
    """Drain gets enough wall-clock past the horizon for timeouts to fire
    and cascades to unwind (livelocks then surface as drain failures)."""
    drain_stop = dp.horizon + 3 * jnp.maximum(dp.wait_timeout, dp.horizon)
    return jnp.where(dp.drain, drain_stop, dp.horizon)


class Derived(NamedTuple):
    us: jnp.ndarray           # (R,) next grantable ticket
    cc: jnp.ndarray           # (R,) commit cursor (lowest uncommitted applied)
    top: jnp.ndarray          # (R,) highest applied ticket (-1 none)
    holder: jnp.ndarray       # (R,) thread holding lowest live ticket (-1)
    n_wait: jnp.ndarray       # (R,) unapplied live tickets (queue length)
    n_live: jnp.ndarray       # (R,) all live tickets
    hotof: jnp.ndarray        # (T,) row of first early applied op (-1)
    napp: jnp.ndarray         # (T,) applied op count per thread


@jax.named_scope("stage_derive")
def _derive(stat: StaticShape, dp: DynParams, th: Threads,
            rows: Rows, ablate: frozenset = frozenset()) -> Derived:
    R = stat.n_rows
    T, L = th.keys.shape
    if "commit_cursor" in ablate:
        # profiler stand-in (DESIGN.md §12): every aggregate at its
        # no-live-ticket value — exact identity on read-only workloads,
        # DCEs the T*L -> R segment reductions otherwise.
        return Derived(
            us=rows.nt, cc=rows.nt, top=jnp.full((R,), NOTK),
            holder=jnp.full((R,), NOTK),
            n_wait=jnp.zeros((R,), I32), n_live=jnp.zeros((R,), I32),
            hotof=jnp.full((T,), NOTK), napp=jnp.zeros((T,), I32))
    live = th.ticket >= 0                                    # (T, L)
    keyf = th.keys

    # A slot's semantics are frozen when applied (th.early); a slot blocks
    # successors' updates unless it applied under early-release semantics.
    blocking = live & (~th.applied | ~th.early)
    us = _seg_min(th.ticket, keyf, R, blocking)
    us = jnp.where(us == INF, rows.nt, us)

    appl = live & th.applied
    # Commit cursor: with group commit, entering the commit queue releases
    # the *order* dependency (the batch syncs together, Fig. 5c); without
    # it, the dependency holds until the commit completes (slot cleared).
    # Brook per-op release retires a slot from the commit order at its
    # last-use op (th.released) — successors may commit ahead of the
    # releaser; the slot stays live/early so the cascade guard still sees
    # it if the releaser is nonetheless forced to abort.
    cc_block = appl & (~th.committing | ~dp.group_commit) & ~th.released
    cc = _seg_min(th.ticket, keyf, R, cc_block)
    cc = jnp.where(cc == INF, us, cc)
    top = _seg_max(th.ticket, keyf, R, appl & ~th.committing)

    tid = jnp.broadcast_to(jnp.arange(T, dtype=I32)[:, None], (T, L))
    enc = th.ticket * I32(T) + tid
    hmin = _seg_min(enc, keyf, R, live)
    holder = jnp.where(hmin == INF, NOTK, hmin % I32(T))

    n_wait = _seg_sum(jnp.ones_like(th.ticket), keyf, R, live & ~th.applied)
    n_live = _seg_sum(jnp.ones_like(th.ticket), keyf, R, live)

    ea = appl & th.early                                     # (T, L)
    first = jnp.argmax(ea, axis=1)
    hotof = jnp.where(ea.any(axis=1),
                      keyf[jnp.arange(T), first], NOTK)
    napp = appl.sum(axis=1).astype(I32)
    return Derived(us, cc, top, holder, n_wait, n_live, hotof, napp)


# ---------------------------------------------------------------------------
# engine step
# ---------------------------------------------------------------------------

class StepEvents(NamedTuple):
    """Per-iteration event masks surfaced by :func:`_make_step_events`.

    Everything here is *already computed* by the step — this tuple only
    names the masks so the obs layer (``repro.obs.trace``) can record
    them into a ring buffer inside the same ``lax.while_loop``. The
    classic entry points drop the tuple on the floor, and XLA dead-code
    eliminates it, so exposing events costs the untraced engine nothing.

    Mask timing: ``grant``/``group_join``/``timeout``/``victim`` describe
    transitions decided at the *start* of the interval (timestamp
    ``t_pre``); ``release``/``commit``/``wait_enter``/``abort`` fire at
    its end (``t_post``). Rows: ``row_cur`` is the thread's current-op
    row for start-of-interval events and ``release``; ``row_begin`` is
    the row of the op begun this iteration (``wait_enter``); ``commit``
    and ``abort`` are thread-level events (row -1 in the trace).

    ``abort`` fires when a rollback COMPLETES, whatever forced it —
    timeout, deadlock victim, injected commit-point abort (``p_abort``),
    cascade, or proactive rollback. ``timeout``/``victim`` only cover
    the first two causes, so without this mask a trace consumer cannot
    partition a thread's events into transaction attempts once aborts
    are injected — the serializability certifier
    (``repro.analysis.isolation``) needs the terminator itself. Its
    timestamp is also the instant the reverts landed and the tickets
    were released (step 6c runs in the same iteration).
    """
    t_pre: jnp.ndarray       # () tick at interval start
    t_post: jnp.ndarray      # () tick at interval end
    row_cur: jnp.ndarray     # (T,) current-op row at interval start
    row_begin: jnp.ndarray   # (T,) row of the op begun this iteration
    grant: jnp.ndarray       # (T,) bool WAIT -> EXEC lock grant
    group_join: jnp.ndarray  # (T,) bool grant joined an open hot group
    timeout: jnp.ndarray     # (T,) bool lock/commit wait timed out
    victim: jnp.ndarray      # (T,) bool chosen as deadlock victim
    release: jnp.ndarray     # (T,) bool brook per-op early release
    commit: jnp.ndarray      # (T,) bool txn committed
    wait_enter: jnp.ndarray  # (T,) bool took a ticket, entered WAIT
    abort: jnp.ndarray       # (T,) bool rollback completed (any cause)


def _make_step_events(stat: StaticShape, dp: DynParams, until=None,
                      ablate: frozenset = frozenset()):
    """Build the tick-step function. ``stat`` is static (shapes + kind);
    every parameter in ``dp`` is traced, so protocol branches are computed
    unconditionally and masked — the price of one program for all configs.

    ``ablate`` (static, control seam only — see :data:`PROF_STAGES` and
    ``repro.obs.prof.STAGE_NOOPS``) names stages whose compute is replaced by a
    shape-correct stand-in so XLA eliminates them from the program. The
    default empty set takes the exact code path that existed before the
    seam — production entry points never pass it.

    ``until`` (traced, segmented mode) caps the *idle* time advance at
    the segment boundary: when no thread is paying work (a pure wait
    window — e.g. a detection-free deadlock whose only pending event is
    a distant timeout) the jump stops at ``until`` instead of skipping
    past it, so a governor can resolve the stall by switching protocol.
    Busy steps are NEVER split: real events may overshoot the boundary
    by one completion, which keeps the step sequence of a segmented run
    literally identical to the single-shot run — several engine rules
    advance per loop iteration (the group-commit queue drains one
    member per derive), so injecting partial iterations into busy
    execution would change event timing. Extra iterations occur only
    inside all-waiting windows, where every stage is a state no-op
    (grantability, aborts, and hotspot transitions are pure functions
    of the frozen state and were already applied at the window's
    opening event; timeouts fire on ``now`` crossings that the idle
    jump never passes) — so only the diagnostic ``Globals.iters`` can
    differ, and only across stall windows split by boundaries.
    """
    T = stat.n_threads
    R = stat.n_rows
    L = stat.txn_len
    ablate = frozenset(ablate)
    assert ablate <= set(PROF_STAGES), sorted(ablate - set(PROF_STAGES))
    tids = jnp.arange(T, dtype=I32)
    tb_bin = jnp.asarray(_TB_PHASE_BIN)
    stop_time = _stop_time(dp)
    idle_stop = stop_time if until is None else jnp.minimum(stop_time,
                                                            until)

    def cur(field_tl, oph):
        """Gather per-thread value at its current op slot (clipped)."""
        return field_tl[tids, jnp.clip(oph, 0, L - 1)]

    def step(s: SimState) -> tuple[SimState, StepEvents]:
        th, rows, g = s
        d = _derive(stat, dp, th, rows, ablate)
        now = g.now

        with jax.named_scope("stage_mark_aborts"):
            cur_key = cur(th.keys, th.op)
            cur_tkt = cur(th.ticket, th.op)
            in_wait = th.phase == WAIT

            # ------------------------------------------------ 1. mark aborts
            forced = th.forced
            # 1a. wait timeout (wait_timeout <= 0 disables both timeouts)
            to = in_wait & ((now - th.wstart) >= dp.wait_timeout)
            to |= (th.phase == CWAIT) & (
                (now - th.wstart) >= dp.commit_wait_timeout)
            to_fire = to & (dp.wait_timeout > 0)
            forced = forced | to_fire
        # 1b. deadlock detection (waits-for cycle walk, up to 8 hops),
        # 2PL-style protocols. One victim per cycle: its max thread id.
        # lax.cond so single-config runs of detection-free protocols skip
        # the walk at runtime; vmapped lanes lower it to a select.
        def _walk_cycle(op):
            in_wait_, phase_, holder_at = op
            succ = jnp.where(in_wait_, holder_at, NOTK)
            succ = jnp.where(succ == tids, NOTK, succ)   # self-wait: none
            walk = succ
            mx = tids
            on_cycle = jnp.zeros_like(in_wait_)
            for _ in range(8):
                ok = walk >= 0
                wi = jnp.where(ok, walk, 0)
                mx = jnp.maximum(mx, jnp.where(ok, walk, -1))
                on_cycle = on_cycle | (ok & (walk == tids))
                # follow only through threads that are themselves waiting
                walk = jnp.where(ok & (phase_[wi] == WAIT),
                                 succ[wi], NOTK)
            return on_cycle & (tids == mx)

        if "deadlock_walk" in ablate:
            # stand-in: no victims (identity when has_detection is False)
            victim = jnp.zeros_like(in_wait)
        else:
            with jax.named_scope("stage_deadlock_walk"):
                victim = lax.cond(dp.has_detection, _walk_cycle,
                                  lambda op: jnp.zeros_like(op[0]),
                                  (in_wait, th.phase, d.holder[cur_key]))
        with jax.named_scope("stage_mark_aborts"):
            forced = forced | victim
            # 1c. proactive hot+non-hot rollback (§4.5)
            hrow = d.hotof
            hold = d.holder[cur_key]
            hold_ok = hold >= 0
            hold_i = jnp.where(hold_ok, hold, 0)
            pro = (in_wait & (hrow >= 0) & hold_ok
                   & ~rows.hot[cur_key]
                   & (d.hotof[hold_i] == hrow) & (hold != tids))
            forced = forced | (pro & dp.proactive_abort)
            # 1d. cascade propagation: any applied early ticket >= casc[key]
            casc_at = rows.casc[th.keys]                          # (T, L)
            hit = (th.applied & th.early & (th.ticket >= 0)
                   & (th.ticket >= casc_at))
            forced = forced | hit.any(axis=1)
            # threads that cannot abort anymore (committing) stay
            forced = forced & (th.phase != COMMIT) & (th.phase != HALT)

            # forced threads with applied early tickets keep cascades open
            # (idempotent marking — covers voluntary commit-point aborts too,
            # which become forced outside this stage)
            casc_src = (th.applied & th.early & (th.ticket >= 0)
                        & forced[:, None])
            casc_min = _seg_min(th.ticket, th.keys, R, casc_src)
            casc = jnp.minimum(rows.casc, casc_min)
            # clear finished cascades: no applied ticket at/above casc remains
            casc = jnp.where((casc < INF) & (d.top < casc), INF, casc)
            rows = rows._replace(casc=casc)
            th = th._replace(forced=forced)

            # -------------------------------------------- 2. divert to RBWAIT
            # forced threads in WAIT/CWAIT park for their cascade turn.
            parkable = forced & ((th.phase == WAIT) | (th.phase == CWAIT))
            phase = jnp.where(parkable, RBWAIT, th.phase)
            th = th._replace(phase=phase,
                             wstart=jnp.where(parkable, now, th.wstart))

        with jax.named_scope("stage_grant_apply"):
            # ------------------------------------------------ 4. grants
            d2 = d  # row aggregates from top of iteration (conservative)
            # 4a. WAIT -> EXEC
            is_w = (th.phase == WAIT) & ~th.forced
            key_w = cur_key
            hot_w = rows.hot[key_w]
        with jax.named_scope("stage_ticket_grant"):
            grantable = (is_w & (cur_tkt == d2.us[key_w])
                         & ~rows.updating[key_w]
                         & (rows.casc[key_w] == INF))
        with jax.named_scope("stage_grant_apply"):
            if "ticket_grant" in ablate:
                # stand-in: nothing grants (identity on read-only workloads,
                # where no thread ever takes a ticket or enters WAIT)
                grantable = jnp.zeros_like(grantable)
            # group locking: leader/follower bookkeeping
            open_leader = rows.gleader[key_w]
            is_leader_grant = (grantable & hot_w & (open_leader == NOTK)
                               & dp.group_lock)
            is_member_grant = (grantable & hot_w & (open_leader != NOTK)
                               & dp.group_lock)

            qlen = d2.n_wait[key_w].astype(F32)
            dd = jnp.where(dp.has_detection,
                           (dp.dd_coeff * qlen).astype(I32), 0)
            hotq = hot_w & dp.hot_queue
            overhead = jnp.where(
                hotq,
                jnp.where(is_leader_grant | ~dp.group_lock,
                          dp.lock_base, dp.grant_cost),
                dp.lock_base + dd)
            work_g = overhead + dp.op_exec

            th = th._replace(
                phase=jnp.where(grantable, EXEC, th.phase),
                work=jnp.where(grantable, work_g, th.work),
                # detection ticks inside this grant's work (tick attribution)
                detleft=jnp.where(grantable, jnp.where(hotq, 0, dd),
                                  th.detleft))
            g = g._replace(
                wait_ticks=g.wait_ticks
                + jnp.sum(jnp.where(grantable, (now - th.wstart), 0)
                          ).astype(F32),
                lock_ops=g.lock_ops
                + jnp.sum(jnp.where(grantable & (~hotq | is_leader_grant),
                                    1, 0)),
                dd_ticks=g.dd_ticks
                + jnp.sum(jnp.where(grantable & ~hotq, dd, 0)))

            upd_new = _seg_max(jnp.ones_like(key_w), key_w, R,
                               grantable) > 0
            rows = rows._replace(updating=rows.updating | upd_new)

        # group bookkeeping: without group locking gleader stays NOTK and
        # gcount 0, so the off branch is the identity (runtime-skipped for
        # single-config non-group runs, select under vmap).
        def _glock_on(op):
            gl, gc = op
            gl = gl.at[key_w].max(jnp.where(is_leader_grant, cur_tkt, NOTK),
                                  mode="drop")
            gc = gc.at[key_w].add(
                jnp.where(is_leader_grant | is_member_grant, 1, 0),
                mode="drop")
            # close full groups; dynamic close when queue drained
            closed_full = gc >= dp.batch_size
            closed_dyn = dp.dynamic_batch & (d2.n_wait == 0) & ~upd_new
            close = (gl != NOTK) & (closed_full | closed_dyn)
            return (jnp.where(close, NOTK, gl), jnp.where(close, 0, gc))

        if "group_hotspot" in ablate:
            gl, gc = rows.gleader, rows.gcount     # forced off branch
        else:
            with jax.named_scope("stage_group_lock"):
                gl, gc = lax.cond(dp.group_lock, _glock_on, lambda op: op,
                                  (rows.gleader, rows.gcount))
        rows = rows._replace(gleader=gl, gcount=gc)

        with jax.named_scope("stage_commit_order"):
            # 4b. CWAIT -> COMMIT (commit order on early rows; leader hold)
            is_cw = (th.phase == CWAIT) & ~th.forced
            live = th.ticket >= 0
            cc_at = d2.cc[th.keys]
            # released slots are OUT of the commit order entirely (brook):
            # the releaser itself must not wait for cc to reach a ticket that
            # cc now skips — only early-but-unreleased slots order commits.
            order_ok = jnp.where(live & th.applied & th.early & ~th.released,
                                 cc_at == th.ticket, True).all(axis=1)
            no_casc = jnp.where(live, rows.casc[th.keys] == INF,
                                True).all(axis=1)
            lead_open = (jnp.where(live & th.applied & th.early,
                                   rows.gleader[th.keys] == th.ticket,
                                   False).any(axis=1)
                         & dp.group_lock)
            can_commit = is_cw & order_ok & no_casc & ~lead_open
            # injected aborts divert to rollback at the commit point
            vol = can_commit & th.willab
            can_commit = can_commit & ~th.willab

            base_cost = dp.commit_base + dp.sync_lat

        # Group commit (Fig. 5c): while a hot row's sync window is in
        # flight, arriving commits of that row join it (binlog group
        # commit semantics); a new window starts only when the device
        # is free, so windows serialize. Amortization factor is thus
        # arrival-limited (~sync_lat / update-chain spacing). Off branch:
        # cost = base, no window bookkeeping.
        def _gcommit_on(op):
            batch_end, batch_n = op
            hrow = d2.hotof
            h_ok = hrow >= 0
            hrow_i = jnp.where(h_ok, hrow, 0)
            be = batch_end[hrow_i]
            join = can_commit & h_ok & (be > now)
            fresh = can_commit & h_ok & ~join
            cost = jnp.where(join, (be - now) + dp.commit_base,
                             jnp.broadcast_to(base_cost, (T,)))
            nbe = batch_end.at[hrow_i].max(
                jnp.where(fresh, now + dp.sync_lat, 0), mode="drop")
            nbn = batch_n.at[hrow_i].add(
                jnp.where(can_commit & h_ok, 1, 0), mode="drop")
            return nbe, nbn, cost

        def _gcommit_off(op):
            return op[0], op[1], jnp.broadcast_to(base_cost, (T,))

        if "group_hotspot" in ablate:
            nbe, nbn, cost = _gcommit_off((rows.batch_end, rows.batch_n))
        else:
            with jax.named_scope("stage_group_commit"):
                nbe, nbn, cost = lax.cond(dp.group_commit
                                          & (dp.sync_lat > 0),
                                          _gcommit_on, _gcommit_off,
                                          (rows.batch_end, rows.batch_n))
        with jax.named_scope("stage_commit_order"):
            rows = rows._replace(batch_end=nbe, batch_n=nbn)
            th = th._replace(
                phase=jnp.where(can_commit, COMMIT,
                                jnp.where(vol, RBWAIT, th.phase)),
                work=jnp.where(can_commit, cost, th.work),
                wstart=jnp.where(vol, now, th.wstart),
                committing=th.committing | (can_commit[:, None] & th.applied),
                forced=th.forced | vol,
                vabort=th.vabort | vol)

        with jax.named_scope("stage_rollback_turn"):
            # -------------------------------------------- 4c. RBWAIT->RBACK
            # (after 4b so voluntary commit-point aborts start their rollback
            # in the same iteration — otherwise dt can jump to a timeout.)
            # my turn iff for my early applied rows the top applied ticket is
            # mine (reverse update order, Alg. 3). No early applied rows => go.
            ea = th.applied & th.early & (th.ticket >= 0)
            top_at = d.top[th.keys]
            my_turn = jnp.where(ea, top_at == th.ticket, True).all(axis=1)
            # multi-row cascade cycles (paper §6.5's excluded case) break via
            # an out-of-order rollback after rb_turn_timeout
            my_turn = my_turn | ((now - th.wstart) >= dp.rb_turn_timeout)
            start_rb = (th.phase == RBWAIT) & my_turn
            rb_work = dp.rb_base + dp.rb_per_op * d.napp
            th = th._replace(
                phase=jnp.where(start_rb, RBACK, th.phase),
                work=jnp.where(start_rb, rb_work, th.work))

        with jax.named_scope("stage_advance"):
            # ------------------------------------------------ 5. dt & advance
            paying = ((th.phase == EXEC) | (th.phase == COMMIT)
                      | (th.phase == RBACK) | (th.phase == BACKOFF)
                      | (th.phase == ARRIVE))
            starting = th.phase == START
            dt_pay = jnp.where(paying, th.work, INF).min()
            texp = jnp.where((in_wait | (th.phase == CWAIT))
                             & (dp.wait_timeout > 0),
                             th.wstart + dp.wait_timeout - now, INF).min()
            rb_exp = jnp.where(th.phase == RBWAIT,
                               th.wstart + dp.rb_turn_timeout - now, INF).min()
            texp = jnp.minimum(texp, jnp.maximum(rb_exp, 1))
            dt = jnp.minimum(dt_pay, jnp.maximum(texp, 1))
            dt = jnp.where(starting.any(), 0, dt)       # starts are instant
            # idle windows (nothing paying) stop at the segment boundary;
            # busy steps keep single-shot event timing (see docstring above)
            cap = jnp.where(dt_pay == INF, idle_stop, stop_time)
            dt = jnp.clip(dt, 0, jnp.maximum(cap - now, 1))
            now = now + dt
            work = jnp.where(paying, th.work - dt, th.work)
            th = th._replace(work=work)

            n_busy = ((th.phase == EXEC) | (th.phase == COMMIT)
                      | (th.phase == RBACK)).sum().astype(F32)
            g = g._replace(now=now, iters=g.iters + 1,
                           busy_ticks=g.busy_ticks + n_busy * dt.astype(F32))

            # --- tick attribution (obs, DESIGN.md §11): charge dt to exactly
            # one TickBreakdown bin per thread. Branch 1 ("hot") when the
            # thread is engaged on a promoted-hot row; EXEC pays its pending
            # detection ticks (detleft, set at grant) before exec proper.
            # Each iteration contributes exactly T*dt across bins, so
            # sum(g.tb) == T * g.now holds at every observation point.
            is_ex = th.phase == EXEC
            ddpay = jnp.where(is_ex, jnp.minimum(th.detleft, dt), 0)
            th = th._replace(detleft=th.detleft - ddpay)
        if "tick_charge" in ablate:
            pass    # stand-in: tb untouched — every other leaf (incl.
            #         detleft above) evolves bit-exactly on ANY config
        else:
            with jax.named_scope("stage_tick_charge"):
                engaged = ((th.phase == WAIT) | is_ex
                           | (th.phase == CWAIT) | (th.phase == COMMIT))
                branch = jnp.where(engaged & rows.hot[cur_key], 1, 0)
                tbf = g.tb.reshape(-1)
                tbf = tbf.at[branch * N_TB + tb_bin[th.phase]].add(
                    jnp.where(is_ex, dt - ddpay, dt))
                tbf = tbf.at[branch * N_TB + TB_DETECT].add(ddpay)
                g = g._replace(tb=tbf.reshape(g.tb.shape))

                # per-record contention attribution (DESIGN.md §14): the
                # masks this iteration already computed, scattered per
                # ROW instead of per phase bin. CA_WAIT uses exactly the
                # mask/time that charges TB_LOCKWAIT (phase still WAIT at
                # stage 5 pays dt at its current-op row), making
                # ca[CA_WAIT].sum() == tb[:, TB_LOCKWAIT].sum() exact.
                # Nothing downstream reads ca, so the off branch leaves
                # every other leaf bit-exact; lax.cond skips the
                # scatters at runtime for attrib-off single-config runs
                # (select under vmap).
                def _ca_on(ca):
                    ca = ca.at[CA_WAIT, cur_key].add(
                        jnp.where(th.phase == WAIT, dt, 0), mode="drop")
                    ca = ca.at[CA_GRANTS, cur_key].add(
                        jnp.where(grantable, 1, 0), mode="drop")
                    ca = ca.at[CA_TIMEOUTS, cur_key].add(
                        jnp.where(to_fire & in_wait, 1, 0), mode="drop")
                    ca = ca.at[CA_VICTIMS, cur_key].add(
                        jnp.where(victim, 1, 0), mode="drop")
                    ca = ca.at[CA_QSUM].add(d.n_wait * dt)
                    ca = ca.at[CA_QMAX].max(d.n_wait)
                    return ca

                g = g._replace(ca=lax.cond(dp.attrib, _ca_on,
                                           lambda ca: ca, g.ca))

        with jax.named_scope("stage_complete"):
            done = paying & (work <= 0)

            # ------------------------------------------------ 6. completions
            # 6a. EXEC done: apply the write, advance op
            e_done = done & (th.phase == EXEC)
            wr_now = cur(th.iswr, th.op) & e_done
            eff_wr = wr_now & ~cur(th.dup, th.op)
            rows = rows._replace(
                applied_val=rows.applied_val.at[cur_key].add(
                    jnp.where(eff_wr, 1, 0), mode="drop"),
                updating=rows.updating & ~(
                    _seg_max(jnp.ones_like(cur_key), cur_key, R, eff_wr) > 0))
            opc = jnp.clip(th.op, 0, L - 1)
            applied = th.applied.at[tids, opc].set(
                jnp.where(eff_wr, True, cur(th.applied, th.op)))
            # freeze the release semantics that were in force when we applied
            early_now = dp.early_all | (dp.early_release & rows.hot[cur_key])
            early = th.early.at[tids, opc].set(
                jnp.where(eff_wr, early_now, cur(th.early, th.op)))
            th = th._replace(applied=applied, early=early)
            # Brook-2PL per-op release (chop.py): when an op completes at its
            # key's LAST use, the key's ticket retires — `early` opens the
            # grant path and `released` drops the commit-order dependency, so
            # successors lock, update, AND commit ahead of the releaser.
            # Gated on ~willab: a txn that will abort at its commit point
            # keeps strict-2PL holds, so no dirty read can ever involve an
            # aborting brook txn — deadlock-free AND cascade-free. If a
            # released txn is nonetheless forced (brook_guard timeouts after
            # a governed switch-in), its early slots open a cascade on the
            # row, which freezes further grants AND commits there (no_casc)
            # until the dependents drain via their own timeouts; successor
            # writes are commutative increments, so the counter invariant
            # survives the out-of-order revert (same argument as
            # rb_turn_timeout in costs.py).
            rel_now = (e_done & cur(th.lastu, th.op) & dp.per_op_release
                       & ~th.forced & ~th.willab)
            rel_slot = ((th.keys == cur_key[:, None]) & (th.ticket >= 0)
                        & rel_now[:, None])
            th = th._replace(
                released=th.released | rel_slot,
                early=th.early | (rel_slot & th.applied))
            nop = th.op + jnp.where(e_done, 1, 0)
            txn_done = e_done & (nop >= th.nops)
            th = th._replace(op=nop)
            # forced threads stop making progress after their op completes
            to_park = e_done & th.forced
            th = th._replace(phase=jnp.where(to_park, RBWAIT, th.phase))
            e_done = e_done & ~to_park
            txn_done = txn_done & ~to_park
            th = th._replace(
                phase=jnp.where(txn_done, CWAIT, th.phase),
                wstart=jnp.where(txn_done, now, th.wstart))
            next_op = e_done & ~txn_done

            # 6b. COMMIT done: release everything, count, next txn
            c_done = done & (th.phase == COMMIT)
            rel = th.ticket >= 0
            committed_w = rel & th.applied & c_done[:, None]
            rows = rows._replace(
                committed_val=rows.committed_val.at[th.keys].add(
                    jnp.where(committed_w, 1, 0), mode="drop"))
            lat = now - th.tstart
            g = g._replace(
                commits=g.commits + c_done.sum(),
                lat_sum=g.lat_sum
                + jnp.where(c_done, lat, 0).sum().astype(F32),
                hist=g.hist.at[_hist_bucket(lat)].add(
                    jnp.where(c_done, 1, 0), mode="drop"))

            # 6c. RBACK done: revert applied writes, release tickets
            r_done = done & (th.phase == RBACK)
            reverted = rel & th.applied & r_done[:, None]
            rows = rows._replace(
                applied_val=rows.applied_val.at[th.keys].add(
                    jnp.where(reverted, -1, 0), mode="drop"))
            g = g._replace(
                user_aborts=g.user_aborts + (r_done & th.vabort).sum(),
                forced_aborts=g.forced_aborts + (r_done & ~th.vabort).sum())

            clear = (c_done | r_done)[:, None]
            th = th._replace(
                ticket=jnp.where(clear, NOTK, th.ticket),
                applied=jnp.where(clear, False, th.applied),
                early=jnp.where(clear, False, th.early),
                committing=jnp.where(clear, False, th.committing),
                released=jnp.where(clear, False, th.released))

            # 6d. BACKOFF done -> START; COMMIT/RBACK -> next
            # backoff is jittered per (thread, txn) to break retry lockstep
            # (identical-key retries re-forming the same deadlock forever)
            b_done = done & (th.phase == BACKOFF)
            jitter = ((tids * I32(40503) + th.txn * I32(9973)) % I32(4) + 1)
            th = th._replace(
                phase=jnp.where(c_done | b_done, START,
                                jnp.where(r_done, BACKOFF, th.phase)),
                work=jnp.where(r_done, dp.backoff * jitter, th.work),
                txn=th.txn + jnp.where(c_done | (r_done & th.vabort), 1, 0),
                retry=jnp.where(r_done & ~th.vabort, True,
                                jnp.where(c_done, False, th.retry)),
                forced=jnp.where(r_done, False, th.forced),
                vabort=jnp.where(r_done, False, th.vabort),
                op=jnp.where(c_done | r_done, 0, nop))

            # 6e. ARRIVE done -> START
            a_done = done & (th.phase == ARRIVE)
            th = th._replace(phase=jnp.where(a_done, START, th.phase))

        # ------------------------------------------------ 7. START new txns
        # A thread halts at the horizon OR when its transaction quota is
        # exhausted (txn_cap; INF in closed loop). The quota check sits
        # exactly where the horizon check does, so a capped thread halts
        # the instant its last credited txn commits (6d set START this
        # same iteration) — the serving layer revives it with new credits
        # at the next segment boundary.
        with jax.named_scope("stage_txn_start"):
            st = th.phase == START
            past = (now >= dp.horizon) | (th.txn >= dp.txn_cap)
            th = th._replace(phase=jnp.where(st & past, HALT, th.phase))
            st = st & ~past
            # fixed-TPS open loop; arrival_rate <= 0: closed loop, no gate.
            # n_active (not the padded T) sets the per-thread arrival interval.
            rate_on = dp.arrival_rate > 0
            interval = jnp.maximum(
                (dp.n_active.astype(F32)
                 / jnp.where(rate_on, dp.arrival_rate, F32(1.0))).astype(I32),
                1)
            arr = th.txn * interval + (tids * 977) % interval
            early_t = st & (arr > now) & rate_on
            th = th._replace(
                phase=jnp.where(early_t, ARRIVE, th.phase),
                work=jnp.where(early_t, arr - now, th.work))
            st = st & ~early_t
        with jax.named_scope("stage_gen_txn"):
            keys, iswr, dup, lastu, nops = gen_txn_dyn(
                stat.kind, R, L, dp.wl, tids, th.txn,
                acq_order=dp.ordered_acquire,
                skip_analysis="dup_analysis" in ablate)
        with jax.named_scope("stage_txn_start"):
            wab = will_abort_dyn(dp.wl.seed, dp.p_abort, tids, th.txn)
            sel = st[:, None]
            th = th._replace(
                keys=jnp.where(sel, keys, th.keys),
                iswr=jnp.where(sel, iswr, th.iswr),
                dup=jnp.where(sel, dup, th.dup),
                lastu=jnp.where(sel, lastu, th.lastu),
                nops=jnp.where(st, nops, th.nops),
                willab=jnp.where(st, wab, th.willab),
                tstart=jnp.where(st & ~th.retry, now, th.tstart),
                op=jnp.where(st, 0, th.op))

        # ------------------------------------------------ 8. begin next op
        # Threads entering a new op (fresh txns or op-advance) either take a
        # ticket (effective write) or execute directly (read / dup write).
        with jax.named_scope("stage_op_begin"):
            begin = st | next_op
            bkey = cur(th.keys, th.op)
            bwr = cur(th.iswr, th.op) & ~cur(th.dup, th.op)
            need_ticket = begin & bwr
            direct = begin & ~bwr
            rd_cost = jnp.where(cur(th.iswr, th.op), dp.op_exec, dp.read_exec)
            th = th._replace(
                phase=jnp.where(direct, EXEC, th.phase),
                work=jnp.where(direct, rd_cost, th.work),
                # direct exec pays no grant overhead: no detection to attribute
                detleft=jnp.where(direct, 0, th.detleft))

        # FIFO ticket assignment with same-tick ranking (sort by key).
        # Sentinel key R sorts all non-takers after every real key so they
        # can never interleave (and break the rank chain) of a key run.
        if "ticket_grant" in ablate:
            # stand-in: no same-tick ranking (exact when need_ticket is
            # everywhere false — read-only workloads take no tickets)
            rank = jnp.zeros((T,), I32)
        else:
            with jax.named_scope("stage_ticket_assign"):
                enc = jnp.where(need_ticket, bkey, I32(R)) * I32(T) + tids
                order = jnp.argsort(enc)
                sk = bkey[order]
                sm = need_ticket[order]
                same = jnp.concatenate([
                    jnp.zeros((1,), bool),
                    (sk[1:] == sk[:-1]) & sm[1:] & sm[:-1]])
                idx = jnp.arange(T)
                seg_start = jnp.where(~same, idx, 0)
                seg_start = lax.associative_scan(jnp.maximum, seg_start)
                rank_sorted = idx - seg_start
                rank = jnp.zeros((T,), I32).at[order].set(
                    rank_sorted.astype(I32))
        with jax.named_scope("stage_op_begin"):
            tkt = jnp.where(need_ticket, rows.nt[bkey] + rank, NOTK)
            counts = _seg_sum(jnp.ones_like(bkey), bkey, R, need_ticket)
            rows = rows._replace(nt=rows.nt + counts)
            th = th._replace(
                ticket=th.ticket.at[tids, jnp.clip(th.op, 0, L - 1)].set(
                    jnp.where(need_ticket, tkt, cur(th.ticket, th.op))),
                phase=jnp.where(need_ticket, WAIT, th.phase),
                wstart=jnp.where(need_ticket, now, th.wstart))

        # ------------------------------------------------ 9. hotspot detect
        # without a hotspot queue rows never turn hot, so the off branch
        # is the identity (runtime-skipped; select under vmap).
        def _hotspot_on(op):
            hot, gleader, gcount = op
            live3 = th.ticket >= 0
            d3_nwait = _seg_sum(jnp.ones_like(th.ticket), th.keys, R,
                                live3 & ~th.applied)
            d3_nlive = _seg_sum(jnp.ones_like(th.ticket), th.keys, R, live3)
            promote = d3_nwait > dp.hot_threshold
            # demote only when the row is fully quiesced: no waiter AND no
            # applied-uncommitted update (the dep list must be empty, §4.1)
            demote = hot & (d3_nlive == 0)
            return ((hot | promote) & ~demote,
                    jnp.where(demote, NOTK, gleader),
                    jnp.where(demote, 0, gcount))

        if "group_hotspot" in ablate:
            hot, gleader, gcount = rows.hot, rows.gleader, rows.gcount
        else:
            with jax.named_scope("stage_hotspot_detect"):
                hot, gleader, gcount = lax.cond(
                    dp.hot_queue, _hotspot_on, lambda op: op,
                    (rows.hot, rows.gleader, rows.gcount))
        rows = rows._replace(hot=hot, gleader=gleader, gcount=gcount)

        ev = StepEvents(
            t_pre=s.g.now, t_post=g.now, row_cur=cur_key, row_begin=bkey,
            grant=grantable, group_join=is_member_grant, timeout=to_fire,
            victim=victim, release=rel_now, commit=c_done,
            wait_enter=need_ticket, abort=r_done)
        return SimState(th, rows, g), ev

    return step


def _make_step(stat: StaticShape, dp: DynParams, until=None,
               ablate: frozenset = frozenset()):
    """Classic step: :func:`_make_step_events` minus the event tuple.

    All non-traced entry points route through this wrapper; XLA DCEs the
    dropped event masks (they are aliases of values the step computes
    anyway), so the split is free. ``ablate`` is the profiler seam
    (:data:`PROF_STAGES`) — production entry points leave it empty.
    """
    step_events = _make_step_events(stat, dp, until=until, ablate=ablate)
    return lambda s: step_events(s)[0]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def init_state_dyn(stat: StaticShape, dp: DynParams) -> SimState:
    """Initial state at the padded shape; padded threads start in HALT."""
    T, L, R = stat.n_threads, stat.txn_len, stat.n_rows
    tids = jnp.arange(T, dtype=I32)
    th = Threads(
        phase=jnp.where(tids < dp.n_active, I32(START), I32(HALT)),
        work=jnp.zeros((T,), I32),
        op=jnp.zeros((T,), I32),
        txn=jnp.zeros((T,), I32),
        tstart=jnp.zeros((T,), I32),
        wstart=jnp.zeros((T,), I32),
        willab=jnp.zeros((T,), bool),
        forced=jnp.zeros((T,), bool),
        vabort=jnp.zeros((T,), bool),
        retry=jnp.zeros((T,), bool),
        keys=jnp.zeros((T, L), I32),
        iswr=jnp.zeros((T, L), bool),
        dup=jnp.zeros((T, L), bool),
        ticket=jnp.full((T, L), NOTK),
        applied=jnp.zeros((T, L), bool),
        early=jnp.zeros((T, L), bool),
        committing=jnp.zeros((T, L), bool),
        lastu=jnp.zeros((T, L), bool),
        released=jnp.zeros((T, L), bool),
        nops=jnp.full((T,), L, I32),
        detleft=jnp.zeros((T,), I32),
    )
    rows = Rows(
        nt=jnp.zeros((R,), I32),
        updating=jnp.zeros((R,), bool),
        hot=jnp.zeros((R,), bool),
        gleader=jnp.full((R,), NOTK),
        gcount=jnp.zeros((R,), I32),
        casc=jnp.full((R,), INF),
        batch_end=jnp.zeros((R,), I32),
        batch_n=jnp.zeros((R,), I32),
        applied_val=jnp.zeros((R,), I32),
        committed_val=jnp.zeros((R,), I32),
    )
    g = Globals(
        now=jnp.asarray(0, I32),
        commits=jnp.asarray(0, I32),
        user_aborts=jnp.asarray(0, I32),
        forced_aborts=jnp.asarray(0, I32),
        lock_ops=jnp.asarray(0, I32),
        wait_ticks=jnp.asarray(0.0, F32),
        busy_ticks=jnp.asarray(0.0, F32),
        lat_sum=jnp.asarray(0.0, F32),
        hist=jnp.zeros((N_HIST,), I32),
        dd_ticks=jnp.asarray(0, I32),
        iters=jnp.asarray(0, I32),
        tb=jnp.zeros((len(TB_BRANCHES), N_TB), I32),
        ca=jnp.zeros((N_CA, R), I32),
    )
    return SimState(th, rows, g)


def init_state(cfg: EngineConfig) -> SimState:
    """Initial state for a single (unpadded) config."""
    return init_state_dyn(*split_config(cfg))


def _run_core(stat: StaticShape, dp: DynParams, s0: SimState,
              until: jnp.ndarray | None = None) -> SimState:
    """The loop itself — shared verbatim by the jitted single-config entry
    point, the vmapped sweep entry point, and the segmented entry points
    (bitwise parity depends on it).

    ``until`` (traced, optional) pauses the loop at the segment boundary:
    it bounds the loop condition AND caps *idle* jumps (see
    :func:`_make_step`), so a stalled system pauses exactly at ``until``
    while a busy one pauses at its first event past it. Busy steps are
    never split, so a segmented run replays the single-shot step
    sequence literally — state and metrics are bit-identical, and even
    ``Globals.iters`` only differs when a fully-idle stall window spans
    boundaries (the jump splits into one iteration per segment).
    """
    with jax.named_scope("loop_setup"):
        step = _make_step(stat, dp, until=until)
        cond = _make_cond(dp, until=until)
    return lax.while_loop(cond, step, s0)


def _make_cond(dp: DynParams, until=None):
    """Loop condition shared by classic and traced runners (obs layer)."""
    stop_time = _stop_time(dp)

    def cond(s: SimState):
        with jax.named_scope("loop_cond"):
            live = (s.th.phase != HALT).any()
            running = jnp.where(dp.drain,
                                live & (s.g.now < stop_time),
                                s.g.now < dp.horizon)
            if until is not None:
                running = running & (s.g.now < until)
            return running & (s.g.iters < dp.max_iters)

    return cond


@functools.partial(jax.jit, static_argnums=0)
def _run_dyn(stat: StaticShape, dp: DynParams, s0: SimState) -> SimState:
    return _run_core(stat, dp, s0)


@functools.partial(jax.jit, static_argnums=0)
def _run_batch(stat: StaticShape, dps: DynParams, s0s: SimState) -> SimState:
    """Run G stacked configs as one program (leading axis on every leaf).

    ``lax.while_loop`` under vmap keeps stepping until every lane's cond is
    false, select-freezing finished lanes — so each lane's final state is
    bit-identical to running it alone at the same (padded) shape.

    Because ``s0s`` is an argument and the loop cond is a pure function of
    the state, this entry point is also *resumable*: passing a paused
    state continues the identical step sequence. The sweep compaction
    scheduler exploits this by capping ``dp.max_iters`` (traced — no
    recompile) at ``iters + slice`` per call, pausing lanes at iteration
    budgets and repacking the unfinished ones; the resulting final states
    are bit-identical to single-shot runs in EVERY leaf including the
    ``iters`` diagnostic (nothing about the orbit changes, only where it
    is observed).
    """
    return jax.vmap(lambda dp, s0: _run_core(stat, dp, s0))(dps, s0s)


def stop_ticks(cfg: EngineConfig) -> int:
    """Host mirror of :func:`_stop_time` for one config."""
    if cfg.drain:
        return cfg.horizon + 3 * max(cfg.protocol.wait_timeout, cfg.horizon)
    return cfg.horizon


def run_finished(cfg: EngineConfig, now: int, iters: int,
                 phase=None) -> bool:
    """Host mirror of :func:`_run_core`'s loop condition (negated).

    The compaction scheduler retires a paused lane exactly when the
    single-shot loop would have exited — keeping the retire decision in
    lockstep with the device cond is what makes compacted results
    bit-identical. ``phase`` (the (T,) thread-phase vector) is only needed
    for ``drain`` runs, whose cond also ends when every thread HALTs.
    """
    if iters >= cfg.max_iters:
        return True
    if cfg.drain:
        live = True if phase is None else bool((np.asarray(phase)
                                                != HALT).any())
        return (not live) or now >= stop_ticks(cfg)
    return now >= cfg.horizon


class SegSnapshot(NamedTuple):
    """Instantaneous contention telemetry at a segment boundary.

    Counter-style telemetry (throughput, aborts, latency, utilization)
    comes from differencing ``Globals`` across the boundary instead
    (:func:`repro.core.lock.metrics.delta_globals`); these are the
    *state* observables a governor cannot recover from counters.
    """
    max_qlen: jnp.ndarray   # () i32  longest row wait queue
    n_hot: jnp.ndarray      # () i32  rows currently promoted hot
    n_live: jnp.ndarray     # () i32  live tickets across all rows
    n_waiting: jnp.ndarray  # () i32  threads in a lock/commit wait phase
    # Distribution observables (obs layer): policies that only see maxima
    # cannot tell one pathological queue from uniform pressure. Both are
    # log2-bucket histograms (bucket 0 = empty, b >= 1 = [2**(b-1), 2**b)):
    wait_hist: jnp.ndarray  # (N_QHIST,) rows by wait-queue depth (sums to R)
    occ_hist: jnp.ndarray   # (N_QHIST,) HOT rows by live-ticket occupancy
    #                         (sums to n_hot)


def _q_bucket(v):
    """log2 occupancy bucket: 0 -> 0, 1 -> 1, [2,4) -> 2, [4,8) -> 3, ..."""
    f = jnp.log2(jnp.maximum(v, 1).astype(F32))
    return jnp.clip(jnp.where(v <= 0, 0, f.astype(I32) + 1), 0, N_QHIST - 1)


def _bucket_counts(bk, weight=None):
    """(N_QHIST,) i32 count of each bucket in ``bk``, only where ``weight``.

    Compare-and-reduce, not a scatter-add: nearly every row falls in
    bucket 0, and the TPU runs colliding scatter updates one at a time
    (~9 ns per row on a TPU v5e at R = 1M), while 12 compares per row
    stream at HBM speed.
    """
    hit = bk[None, :] == jnp.arange(N_QHIST, dtype=I32)[:, None]
    if weight is not None:
        hit = hit & weight[None, :]
    return hit.sum(axis=1, dtype=I32)


@jax.named_scope("seg_snapshot")
def _snapshot(stat: StaticShape, dp: DynParams, s: SimState) -> SegSnapshot:
    d = _derive(stat, dp, s.th, s.rows)
    waitish = ((s.th.phase == WAIT) | (s.th.phase == CWAIT)
               | (s.th.phase == RBWAIT))
    return SegSnapshot(
        max_qlen=d.n_wait.max().astype(I32),
        n_hot=s.rows.hot.sum().astype(I32),
        n_live=d.n_live.sum().astype(I32),
        n_waiting=waitish.sum().astype(I32),
        wait_hist=_bucket_counts(_q_bucket(d.n_wait)),
        occ_hist=_bucket_counts(_q_bucket(d.n_live), s.rows.hot))


def _run_seg_core(stat: StaticShape, dp: DynParams, s0: SimState,
                  until: jnp.ndarray) -> tuple[SimState, SegSnapshot]:
    s = _run_core(stat, dp, s0, until=until)
    return s, _snapshot(stat, dp, s)


@functools.partial(jax.jit, static_argnums=0)
def _run_seg_dyn(stat: StaticShape, dp: DynParams, s0: SimState,
                 until: jnp.ndarray) -> tuple[SimState, SegSnapshot]:
    return _run_seg_core(stat, dp, s0, until)


@functools.partial(jax.jit, static_argnums=0)
def _run_seg_batch(stat: StaticShape, dps: DynParams, s0s: SimState,
                   untils: jnp.ndarray) -> tuple[SimState, SegSnapshot]:
    """Segmented analogue of :func:`_run_batch`: G lanes, one program.

    Every argument including ``untils`` is traced, so a governor can
    re-decide any lane's protocol, workload, or boundary between segments
    and re-enter the *same* executable — zero recompiles per shape bucket.
    """
    return jax.vmap(
        lambda dp, s0, u: _run_seg_core(stat, dp, s0, u))(dps, s0s, untils)


def run_segment(stat: StaticShape, dp: DynParams, state: SimState,
                until) -> tuple[SimState, SegSnapshot]:
    """Advance ``state`` until sim-time reaches ``until`` (or the run ends).

    Returns the resumable state plus an end-of-segment telemetry snapshot.
    A run split into N segments with unchanged ``dp`` is bit-identical to
    the single-shot :func:`run_sim`/``simulate`` result in every state
    leaf and metric — the boundary pauses the ``while_loop`` between
    events (busy systems stop at their first event past ``until``, fully
    stalled ones exactly at it); it never moves or splits an event. The
    diagnostic ``Globals.iters`` can differ only when a stall window
    spans boundaries. Changing ``dp`` (protocol preset, costs, workload)
    between segments is free: everything in it is traced, so the
    compiled program is reused.
    """
    return _run_seg_dyn(stat, dp, state, jnp.asarray(until, I32))


def run_sim(cfg: EngineConfig) -> SimState:
    """Run a simulation to completion and return the final state.

    The host work of the call sits in ``repro.engine.*`` profiler spans
    (no-ops unless ``jax.profiler`` is tracing); ``run`` covers the enqueue
    only, the device runs on after it returns."""
    with TraceAnnotation("repro.engine.split_config"):
        stat, dp = split_config(cfg)
    with TraceAnnotation("repro.engine.init_state"):
        s0 = init_state_dyn(stat, dp)
    with TraceAnnotation("repro.engine.run"):
        return _run_dyn(stat, dp, s0)


def simulate(protocol: str, workload: WorkloadSpec, n_threads: int,
             costs: CostModel | None = None, horizon: int = 2_000_000,
             p_abort: float = 0.0, drain: bool = False, seed: int = 0,
             attrib: bool = False, **proto_over) -> SimState:
    """Convenience entry point: run one protocol over one workload."""
    cfg = EngineConfig(
        protocol=protocol_params(protocol, **proto_over),
        costs=costs or CostModel(),
        workload=workload,
        n_threads=n_threads,
        horizon=horizon,
        p_abort=p_abort,
        drain=drain,
        seed=seed,
        attrib=attrib,
    )
    return run_sim(cfg)
