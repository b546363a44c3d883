"""Plain event-by-event reference of a served pool under strict two-phase
locking with deadlock detection (the ``mysql`` protocol), in numpy.

It imports nothing of the program. From the same arrivals, the same
workload definition (``bench/reference.py``'s copy of the key generator)
and the protocol constants written out below, it runs the pool event by
event and returns what users of a served run get: every request's response
time, and per boundary the commits, the requests arrived, rejected and
completed, the queue, the requests in flight and the thread-ticks per
attribution bin. The timed path must give the same answers exactly.

The rules it follows, per step of simulated time:

* every row's lock queue is a ticket queue: a writer takes the row's next
  ticket (same-instant takers in thread order) and is granted when its
  ticket is the lowest live ticket of the row and no update of the row is
  executing; locks are held to commit (strict 2PL);
* a grant costs ``lock_base`` plus ``dd_coeff`` ticks per waiter queued on
  the row (the detection scan), then ``op_exec`` of work; the scan ticks
  are charged to the detection bin first;
* each waiter waits for the holder of its row; a waits-for cycle of up to
  8 hops loses its highest thread id, which rolls back (``rb_base`` plus
  ``rb_per_op`` per applied write), backs off (``backoff`` times a jitter
  of 1-4 from thread and transaction) and retries the same transaction;
  a wait longer than ``wait_timeout`` rolls back too;
* commit costs ``commit_base + sync_lat`` and releases every lock;
* time jumps to the next completion or timeout; with nothing running it
  stops at the segment boundary;
* at each boundary the host admits arrivals into a bounded queue (reject
  past it), hands queued requests to threads as credits (round-robin,
  fewest outstanding first, at most ``max_outstanding`` each), and
  matches each thread's completed transactions to its requests, oldest
  first: a response time is the boundary's simulated time less the
  arrival's. A thread with no credit left halts; new credit wakes it.
"""
from __future__ import annotations

from collections import deque

import numpy as np

from bench import reference

INF = 2 ** 30
NOTK = -1
(START, WAIT, EXEC, CWAIT, COMMIT, RBACK, RBWAIT, BACKOFF, ARRIVE,
 HALT) = range(10)
BINS = ("exec", "lock_wait", "commit_wait", "rollback", "detection", "sync",
        "idle")
B_DETECT = 4
# phase -> attribution bin
PHASE_BIN = np.array([6, 1, 0, 2, 5, 3, 3, 3, 6, 6])
MAX_ITERS = 1_500_000
MIN_PAD = 64

# strict 2PL with deadlock detection: the protocols this reference runs
PROTOCOLS = {"mysql": reference.PROTOCOL["mysql"]}


def padded_threads(n: int) -> int:
    """Device thread slots of a pool of ``n``: a power of two, 64 or more."""
    v = max(int(n), MIN_PAD)
    return 1 << (v - 1).bit_length()


class Pool:
    """Threads and rows of one served pool, stepped as the rules say."""

    def __init__(self, wl: dict, seed: int, hot_base: int, costs: dict,
                 proto: dict, n_threads: int, horizon: int, cdf=None):
        self.wl, self.seed, self.hot_base, self.cdf = wl, seed, hot_base, cdf
        self.c, self.p = costs, proto
        self.n, self.T = n_threads, padded_threads(n_threads)
        self.L, R = int(wl["txn_len"]), int(wl["n_rows"])
        self.horizon = horizon
        T, L = self.T, self.L
        self.tids = np.arange(T)
        self.phase = np.where(self.tids < n_threads, START, HALT)
        z = lambda: np.zeros(T, np.int64)
        self.work, self.op, self.txn = z(), z(), z()
        self.tstart, self.wstart, self.detleft = z(), z(), z()
        self.cap = z()
        self.forced = np.zeros(T, bool)
        self.retry = np.zeros(T, bool)
        self.keys = np.zeros((T, L), np.int64)
        self.iswr = np.zeros((T, L), bool)
        self.dup = np.zeros((T, L), bool)
        self.ticket = np.full((T, L), NOTK, np.int64)
        self.applied = np.zeros((T, L), bool)
        self.nt = np.zeros(R, np.int64)
        self.updating = np.zeros(R, bool)
        self.now = self.iters = 0
        self.commits = self.forced_aborts = 0
        self.tb = np.zeros(len(BINS), np.int64)

    # -- the rows' queues, read off the live tickets --
    def _queues(self, at_keys):
        """Per key of ``at_keys``: the lowest live ticket (the row's next
        ticket when none is live), the thread holding it (-1 none), and the
        number of queued, not yet applied tickets."""
        live = self.ticket >= 0
        if not live.any():
            none = np.zeros(at_keys.shape, np.int64)
            return self.nt[at_keys], none + NOTK, none
        lk, lt = self.keys[live], self.ticket[live]
        ltid = np.nonzero(live)[0]
        waiting = ~self.applied[live]
        uk, inv = np.unique(lk, return_inverse=True)
        order = np.lexsort((lt, inv))          # by row, then ticket
        lead = np.r_[True, inv[order][1:] != inv[order][:-1]]
        low, first = lt[order][lead], ltid[order][lead]
        n_wait = np.bincount(inv, weights=waiting, minlength=uk.size)
        pos = np.minimum(np.searchsorted(uk, at_keys), uk.size - 1)
        have = uk[pos] == at_keys
        us = np.where(have, low[pos], self.nt[at_keys])
        holder = np.where(have, first[pos], NOTK)
        qlen = np.where(have, n_wait[pos], 0).astype(np.int64)
        return us, holder, qlen

    def _new_txns(self, who):
        tids = self.tids[who]
        keys, iswr = reference.txn_keys(self.wl, self.seed, self.hot_base,
                                        tids, self.txn[who], self.cdf)
        self.keys[who] = keys
        self.iswr[who] = iswr
        self.dup[who] = iswr & ~reference.effective_writes(keys, iswr)

    def step(self, until: int) -> None:
        c, p, T = self.c, self.p, self.T
        tids, now = self.tids, self.now
        slot = np.clip(self.op, 0, self.L - 1)
        cur_key = self.keys[tids, slot]
        cur_tkt = self.ticket[tids, slot]
        us, holder, qlen = self._queues(cur_key)
        napp = self.applied.sum(axis=1)
        phase0 = self.phase.copy()
        in_wait = phase0 == WAIT

        # aborts: timeouts, then the waits-for cycle walk
        forced = self.forced.copy()
        if p["wait_timeout"] > 0:
            forced |= in_wait & (now - self.wstart >= p["wait_timeout"])
            forced |= (phase0 == CWAIT) & (
                now - self.wstart >= p["commit_wait_timeout"])
        succ = np.where(in_wait, holder, NOTK)
        succ = np.where(succ == tids, NOTK, succ)
        if (succ >= 0).any():
            walk, top = succ.copy(), tids.copy()
            on_cycle = np.zeros(T, bool)
            for _ in range(8):
                ok = walk >= 0
                wi = np.where(ok, walk, 0)
                top = np.maximum(top, np.where(ok, walk, -1))
                on_cycle |= ok & (walk == tids)
                walk = np.where(ok & (phase0[wi] == WAIT), succ[wi], NOTK)
            forced |= on_cycle & (tids == top)
        forced &= (phase0 != COMMIT) & (phase0 != HALT)
        self.forced = forced
        park = forced & ((phase0 == WAIT) | (phase0 == CWAIT))
        self.phase[park] = RBWAIT
        self.wstart[park] = now

        # grants
        grant = ((self.phase == WAIT) & ~forced & (cur_tkt == us)
                 & ~self.updating[cur_key])
        dd = (p["dd_coeff"] * qlen).astype(np.int64)
        self.phase[grant] = EXEC
        self.work[grant] = (p["lock_base"] + dd + c["op_exec"])[grant]
        self.detleft[grant] = dd[grant]
        self.updating[cur_key[grant]] = True
        # commits
        com = (self.phase == CWAIT) & ~forced
        self.phase[com] = COMMIT
        self.work[com] = c["commit_base"] + c["sync_lat"]
        # rollbacks
        rb = self.phase == RBWAIT
        self.phase[rb] = RBACK
        self.work[rb] = c["rb_base"] + c["rb_per_op"] * napp[rb]

        # advance to the next event
        ph = self.phase
        paying = np.isin(ph, (EXEC, COMMIT, RBACK, BACKOFF, ARRIVE))
        dt_pay = int(self.work[paying].min()) if paying.any() else INF
        texp = INF
        if p["wait_timeout"] > 0:
            w = in_wait | (ph == CWAIT)
            if w.any():
                texp = int((self.wstart[w] + p["wait_timeout"] - now).min())
        dt = min(dt_pay, max(texp, 1))
        if (ph == START).any():
            dt = 0
        cap = min(self.horizon, until) if dt_pay == INF else self.horizon
        dt = min(max(dt, 0), max(cap - now, 1))
        now += dt
        self.now = now
        self.iters += 1
        self.work[paying] -= dt
        is_ex = ph == EXEC
        ddpay = np.where(is_ex, np.minimum(self.detleft, dt), 0)
        self.detleft -= ddpay
        np.add.at(self.tb, PHASE_BIN[ph], np.where(is_ex, dt - ddpay, dt))
        self.tb[B_DETECT] += int(ddpay.sum())
        done = paying & (self.work <= 0)

        # an update completes: apply it, go to the next op or to commit
        e_done = done & is_ex
        wr = e_done & self.iswr[tids, slot] & ~self.dup[tids, slot]
        self.updating[cur_key[wr]] = False
        self.applied[tids[wr], slot[wr]] = True
        nop = self.op + e_done
        txn_done = e_done & (nop >= self.L)
        to_park = e_done & forced
        self.phase[to_park] = RBWAIT
        e_done &= ~to_park
        txn_done &= ~to_park
        self.phase[txn_done] = CWAIT
        self.wstart[txn_done] = now
        next_op = e_done & ~txn_done
        # a commit or a rollback completes: its locks go
        c_done = done & (self.phase == COMMIT)
        self.commits += int(c_done.sum())
        r_done = done & (self.phase == RBACK)
        self.forced_aborts += int(r_done.sum())
        gone = c_done | r_done
        self.ticket[gone] = NOTK
        self.applied[gone] = False
        b_done = done & (self.phase == BACKOFF)
        jitter = (tids * 40503 + self.txn * 9973) % 4 + 1
        self.phase[c_done | b_done] = START
        self.phase[r_done] = BACKOFF
        self.work[r_done] = (c["backoff"] * jitter)[r_done]
        self.txn += c_done
        self.retry[r_done] = True
        self.retry[c_done] = False
        self.forced[r_done] = False
        self.op = np.where(gone, 0, nop)

        # new transactions: halt at the horizon or when out of credit
        st = self.phase == START
        past = (now >= self.horizon) | (self.txn >= self.cap)
        self.phase[st & past] = HALT
        st &= ~past
        if st.any():
            self._new_txns(st)
        self.tstart[st & ~self.retry] = now
        self.op[st] = 0

        # begin the next op: a write takes a ticket, a repeat runs at once
        begin = st | next_op
        slot = np.clip(self.op, 0, self.L - 1)
        bkey = self.keys[tids, slot]
        bwr = self.iswr[tids, slot] & ~self.dup[tids, slot]
        direct = begin & ~bwr
        self.phase[direct] = EXEC
        self.work[direct] = np.where(self.iswr[tids, slot], c["op_exec"],
                                     c["read_exec"])[direct]
        self.detleft[direct] = 0
        take = np.flatnonzero(begin & bwr)
        if take.size:
            k = bkey[take]
            order = np.lexsort((take, k))
            ks = k[order]
            run_start = np.r_[True, ks[1:] != ks[:-1]]
            idx = np.arange(ks.size)
            rank = idx - np.maximum.accumulate(np.where(run_start, idx, 0))
            tkt = np.empty(take.size, np.int64)
            tkt[order] = self.nt[ks] + rank
            np.add.at(self.nt, k, 1)
            self.ticket[take, slot[take]] = tkt
            self.phase[take] = WAIT
            self.wstart[take] = now

    def run_until(self, until: int) -> None:
        while (self.now < self.horizon and self.now < until
               and self.iters < MAX_ITERS):
            self.step(until)


def serve_call(wl: dict, seed: int, hot_base: int, costs: dict, proto: dict,
               n_threads: int, times: np.ndarray, seg: int, n_bounds: int,
               queue_cap: int, max_outstanding: int, cdf=None) -> dict:
    """One served call, as the reference runs it: the records of every
    boundary, every response time in ticks (in the order they are
    observed), and the call's totals."""
    horizon = seg * n_bounds
    bounds = list(range(seg, horizon, seg)) + [horizon]
    pool = Pool(wl, seed, hot_base, costs, proto, n_threads, horizon, cdf)
    n = n_threads
    queue: deque = deque()
    assigned = [deque() for _ in range(n)]
    caps = np.zeros(n, np.int64)
    seen = np.zeros(n, np.int64)
    ptr = 0
    responses: list[int] = []

    def admit(boundary):
        nonlocal ptr
        arrived = rejected = 0
        while ptr < times.size and times[ptr] <= boundary:
            arrived += 1
            if len(queue) < queue_cap:
                queue.append(int(times[ptr]))
            else:
                rejected += 1
            ptr += 1
        return arrived, rejected

    def dispatch():
        out = caps - seen
        while queue:
            moved = False
            for t in sorted(range(n), key=lambda t: (out[t], t)):
                if not queue:
                    break
                if out[t] >= max_outstanding:
                    continue
                assigned[t].append(queue.popleft())
                caps[t] += 1
                out[t] += 1
                moved = True
            if not moved:
                break

    pre = admit(0)
    dispatch()
    records = []
    commits0, tb0 = 0, pool.tb.copy()
    for k, until in enumerate(bounds):
        pool.cap[:n] = caps
        if k:
            wake = np.zeros(pool.T, bool)
            wake[:n] = caps > seen
            pool.phase[wake & (pool.phase == HALT)] = START
        pool.run_until(until)
        t1 = pool.now
        completed = 0
        for t in range(n):
            d = int(pool.txn[t] - seen[t])
            if d > len(assigned[t]):
                raise AssertionError(f"slot {t}: {d} completions vs "
                                     f"{len(assigned[t])} requests")
            for _ in range(d):
                responses.append(t1 - assigned[t].popleft())
            completed += d
        seen[:] = pool.txn[:n]
        arrived, rejected = admit(until)
        if k == 0:
            arrived, rejected = arrived + pre[0], rejected + pre[1]
        dispatch()
        records.append({
            "t1": t1, "commits": pool.commits - commits0,
            "arrived": arrived, "rejected": rejected,
            "completed": completed, "qlen": len(queue),
            "in_flight": int((caps - seen).sum()),
            "breakdown": tuple(int(v) for v in pool.tb - tb0)})
        commits0, tb0 = pool.commits, pool.tb.copy()
    return {"records": records, "responses": responses,
            "commits": pool.commits, "forced_aborts": pool.forced_aborts,
            "txn": pool.txn[:n].copy()}


def mismatch(want: dict, got: dict) -> int:
    """Answers of a served call that differ from the reference's: one per
    differing field of a boundary record, per differing response (and per
    response missing on either side), and per differing total."""
    bad = abs(len(want["records"]) - len(got["records"]))
    for a, b in zip(want["records"], got["records"]):
        bad += sum(a[k] != b[k] for k in a)
    ra, rb = want["responses"], got["responses"]
    bad += abs(len(ra) - len(rb))
    bad += sum(x != y for x, y in zip(ra, rb))
    bad += int(want["commits"] != got["commits"])
    bad += int(want["forced_aborts"] != got["forced_aborts"])
    bad += int((np.asarray(want["txn"]) != np.asarray(got["txn"])).sum())
    return bad
