"""Plain reference of what a simulated run must have done, in numpy.

It imports nothing of the program. Given the transactions the program
says each client thread completed (its ``txn`` counter), it regenerates
their row keys from the workload's definition (the splitmix32 hash and the
Zipf table of ``repro.core.lock.workload``, copied here) and applies them
serially, one increment per distinct row written. The program's answer is
its row counters; the reference's is the serial replay. They must agree on
every row (no lost update, no phantom write). It also checks what the
configuration guarantees of any instant of a run: the rows' applied-but-
uncommitted increments are exactly the live applied locks of the threads'
current transactions, and every thread-tick is charged to exactly one bin.

``chain_ticks`` copies the steady-state serial chain of the analytic oracle
(``repro.core.lock.ref_engine``) for single-hot-row transactions under group
locking, with the protocol constants of ``repro.core.lock.costs`` written
out.
"""
from __future__ import annotations

import dataclasses

import numpy as np

U32 = np.uint32
MOD32 = 1 << 32

# protocol constants of repro.core.lock.costs.protocol_params, as the
# cells run them: group locking (the hot-row chain) and strict 2PL with
# deadlock detection (bench/served_2pl.py)
PROTOCOL = {
    "group": dict(lock_base=4, grant_cost=2, batch_size=10),
    "mysql": dict(lock_base=12, dd_coeff=3.0, wait_timeout=500_000,
                  commit_wait_timeout=500_000),
}


@dataclasses.dataclass
class CallFinal:
    """What one call left behind, on the host: the answers under check."""
    seed: int               # WorkloadSpec.seed of the call
    hot_base: int           # WorkloadSpec.hot_base of the call
    t_pad: int              # padded thread count of the program's arrays
    txn: np.ndarray         # (T_pad,) completed transactions per thread
    ticket: np.ndarray      # (T_pad, L) live lock tickets (-1 none)
    applied: np.ndarray     # (T_pad, L) slot's write applied
    applied_val: np.ndarray  # (R,) net applied increments per row
    committed_val: np.ndarray  # (R,) committed increments per row
    tb: np.ndarray          # thread-tick attribution bins
    now: int                # simulated ticks at the end of the call
    commits: int
    user_aborts: int
    iters: int


# --- the workload's key generation (repro.core.lock.workload, copied) ---

def _hash_u32(x: np.ndarray) -> np.ndarray:
    x = x.astype(U32)
    x = (x ^ (x >> U32(16))) * U32(0x7FEB352D)
    x = (x ^ (x >> U32(15))) * U32(0x846CA68B)
    return x ^ (x >> U32(16))


def _hash3(a, b, c, salt: int) -> np.ndarray:
    salt = U32(salt % MOD32)
    h = _hash_u32(a.astype(U32) * U32(0x9E3779B9) + salt)
    h = _hash_u32(h ^ (b.astype(U32) * U32(0x85EBCA6B)))
    return _hash_u32(h ^ (c.astype(U32) * U32(0xC2B2AE35)))


def _uniform01(h: np.ndarray) -> np.ndarray:
    return h.astype(np.float32) * np.float32(1.0 / 4294967296.0)


def zipf_cdf(n: int, s: float) -> np.ndarray:
    """Zipf(s) CDF over keys [0, n): float64 weights, float32 table."""
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** (-float(s)) if s > 0 else np.ones_like(ranks)
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    return cdf.astype(np.float32)


def txn_keys(wl: dict, seed: int, hot_base: int, tids: np.ndarray,
             ctrs: np.ndarray, cdf: np.ndarray | None = None):
    """Row keys (N, L) and write flags (N, L) of transaction ``ctrs[i]`` of
    thread ``tids[i]``, for the workload ``wl`` (a configuration's
    ``workload`` block)."""
    kind, R, L = wl["kind"], int(wl["n_rows"]), int(wl["txn_len"])
    tid = tids.astype(np.int64)[:, None]
    ctr = ctrs.astype(np.int64)[:, None]
    slot = np.arange(L, dtype=np.int64)[None, :]
    base = (tid * 1_000_003 + ctr) % MOD32
    base, slot_b = np.broadcast_arrays(base, slot)
    hk = _hash3(base, slot_b, np.zeros_like(slot_b), seed * 7 + 1)
    hw = _hash3(base, slot_b, np.ones_like(slot_b), seed * 7 + 2)
    u_key, u_wr = _uniform01(hk), _uniform01(hw)
    wr = u_wr < np.float32(wl.get("write_ratio", 1.0))
    hb = hot_base % R
    if kind == "hotspot_update":
        k_rest = np.clip(1 + (u_key * np.float32(R - 1)).astype(np.int64),
                         1, R - 1)
        k_rest = np.where(k_rest == hb, 0, k_rest)
        keys = np.where(slot_b == 0, hb, k_rest)
        iswr = np.where(slot_b == 0, True, wr)
    elif kind == "zipf":
        if cdf is None:
            cdf = zipf_cdf(R, wl["zipf_s"])
        keys = (np.clip(np.searchsorted(cdf, u_key, side="left"), 0, R - 1)
                + hb) % R
        iswr = np.ones_like(wr)
    else:
        raise NotImplementedError(f"no reference for workload {kind!r}")
    return keys.astype(np.int64), iswr.astype(bool)


def effective_writes(keys: np.ndarray, iswr: np.ndarray) -> np.ndarray:
    """Writes that take a lock: the first write of each row in a txn."""
    L = keys.shape[1]
    eq = keys[:, :, None] == keys[:, None, :]
    earlier = np.tril(np.ones((L, L), dtype=bool), k=-1)[None]
    dup = np.any(eq & earlier & iswr[:, None, :], axis=2) & iswr
    return iswr & ~dup


# --- the comparison ---

def replay_rows(wl: dict, call: CallFinal, cdf=None) -> np.ndarray:
    """Per-row committed increments of a serial replay of the committed
    transactions (every completed txn commits: no injected aborts)."""
    n = call.txn.astype(np.int64)
    tids = np.repeat(np.arange(n.size), n)
    ctrs = np.concatenate([np.arange(k) for k in n]) if n.sum() else \
        np.zeros(0, np.int64)
    R = int(wl["n_rows"])
    if tids.size == 0:
        return np.zeros(R, np.int64)
    keys, iswr = txn_keys(wl, call.seed, call.hot_base, tids, ctrs, cdf)
    eff = effective_writes(keys, iswr)
    return np.bincount(keys[eff], minlength=R)


def numbers(wl: dict, call: CallFinal, cdf=None) -> dict:
    """The exact numbers of one call: each is 0 on a sound run."""
    R = int(wl["n_rows"])
    committed = call.committed_val.astype(np.int64)
    want = replay_rows(wl, call, cdf)
    row_mismatch = int((want != committed).sum())

    tids = np.arange(call.t_pad)
    cur_keys, _ = txn_keys(wl, call.seed, call.hot_base, tids,
                           call.txn.astype(np.int64), cdf)
    live = (call.ticket >= 0) & call.applied
    inflight = np.bincount(cur_keys[live], minlength=R)
    pending = call.applied_val.astype(np.int64) - committed
    inflight_mismatch = int((pending != inflight).sum())

    d = (int(call.tb.astype(np.int64).sum()) - call.t_pad * call.now) % MOD32
    tick_gap = min(d, MOD32 - d)
    ledger_gap = abs(call.commits + call.user_aborts - int(call.txn.sum()))
    return {"row_mismatch": row_mismatch,
            "inflight_mismatch": inflight_mismatch,
            "tick_gap": tick_gap, "ledger_gap": ledger_gap}


def chain_ticks(protocol: str, n_threads: int, costs: dict) -> float:
    """Ticks per commit of one hot row's serial chain at saturation, for
    group locking: a member's grant and update, and its share of the
    leader's lock (the commit syncs in the group's batch)."""
    if protocol != "group" or n_threads < 2:
        raise NotImplementedError(f"no chain for {protocol!r} at "
                                  f"{n_threads} threads")
    p = PROTOCOL[protocol]
    return p["grant_cost"] + costs["op_exec"] + p["lock_base"] / p["batch_size"]
