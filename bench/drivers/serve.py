"""Open loop: back-to-back ``serve()`` calls of one pool under Poisson load.

Traffic keys: ``protocol`` (a serving preset), ``seg_ticks`` (simulated
ticks between boundaries), ``boundaries`` (per call), ``rate_per_tick``
(offered requests per simulated tick), ``quiet_boundaries`` (trailing
boundaries of each call with no new arrivals, so that every admitted
request is due an answer before the call ends), ``queue_cap_per_thread``,
``warm_boundaries``.

Arrivals are on the simulated clock, from the benchmark's own Poisson
generator seeded per call. Admission rejects past the queue bound, and
``max_outstanding`` is ``max(8, 2 * seg * rate / T + 1)``, as the serving
figure sets it. The boundary clock is a ``metrics_registry`` observer:
``serve`` calls it once per boundary, and it records the host time.

After the window, :func:`compare` runs every call again in the plain
event-by-event reference (``bench/served_2pl.py``) on the same arrivals
and holds the program's answers to it: each boundary's record, every
response time, the commits, rollbacks and transactions per thread.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import arrivals, calls, reference, served_2pl
from bench.stats import percentile


class BoundaryClock:
    """``metrics_registry`` for ``serve``: the host time of each boundary,
    with one benchmark span per boundary interval when tracing."""

    def __init__(self, tracer):
        self.tracer = tracer
        self.times: list[float] = []
        self._span = None

    def start(self) -> None:
        self.times = [time.perf_counter()]
        self._open()

    def _open(self) -> None:
        self._span = self.tracer.span("boundary")
        self._span.__enter__()

    def close(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def observe(self, cell_name: str, record) -> None:
        self.times.append(time.perf_counter())
        self.close()
        self._open()

    @property
    def intervals_ms(self) -> list[float]:
        return [1e3 * (b - a) for a, b in zip(self.times, self.times[1:])]


def _max_outstanding(seg: int, rate: float, T: int) -> int:
    return max(8, int(2 * seg * rate / T) + 1)


def _serve(ctx, seed: int, n_bounds: int, clock: BoundaryClock | None):
    from repro.serving import ArrivalSchedule, ServeCell, serve
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    T, seg, rate = cfg["n_threads"], int(tr["seg_ticks"]), tr["rate_per_tick"]
    horizon = seg * n_bounds
    quiet = min(int(tr["quiet_boundaries"]), n_bounds - 1)
    times = arrivals.poisson(rate, horizon - quiet * seg, seed)
    cell = ServeCell(
        name=ctx.cell.name,
        schedule=ArrivalSchedule("poisson", times, horizon, seed),
        workload=calls.workload_spec(cfg, seed), n_threads=T,
        preset=tr["protocol"], costs=calls.costs(cfg),
        queue_cap=int(tr["queue_cap_per_thread"]) * T, admission="reject",
        max_outstanding=_max_outstanding(seg, rate, T))
    if clock is not None:
        clock.start()
    try:
        res = serve([cell], seg_ticks=seg, return_states=True,
                    keep_responses=True, metrics_registry=clock)
    finally:
        if clock is not None:
            clock.close()
    state = res.states[cell.name]
    jax.block_until_ready(state)
    return res, state, cell, times


def _warm_hist(ctx) -> None:
    """Compile the response-histogram fold at every padded width a boundary
    can reach (64 up to the pool's credit capacity), so that a boundary
    with many completions compiles nothing inside the window. The
    histogram is a device array, as in ``serve``: a numpy one would warm
    another executable."""
    import jax.numpy as jnp
    from repro.serving import runner
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    T = cfg["n_threads"]
    cap = T * _max_outstanding(int(tr["seg_ticks"]), tr["rate_per_tick"], T)
    hist = jnp.zeros((runner.N_HIST,), jnp.int32)
    w = 64
    while True:
        hist = runner._resp_hist_update(hist, [1] * w)
        if w >= cap:
            break
        w *= 2


def run(ctx: calls.Context) -> calls.Outcome:
    from repro.obs import compile_log
    tr = ctx.cell.traffic
    _serve(ctx, calls.call_seed(ctx.seed, -1), int(tr["warm_boundaries"]),
           None)
    _warm_hist(ctx)
    ctx.setup_done()

    before = compile_log.snapshot()
    finals, numbers, intervals, answers = [], [], [], []
    commits = offered = failed = 0
    counters = {}
    n_bounds = int(tr["boundaries"])
    t0 = time.perf_counter()
    k = 0
    while k == 0 or time.perf_counter() - t0 < ctx.seconds:
        seed = calls.call_seed(ctx.seed, k)
        clock = BoundaryClock(ctx.tracer)
        if k == 0:
            ctx.tracer.start()
        with ctx.tracer.span("call"):
            res, state, cell, times = _serve(ctx, seed, n_bounds, clock)
        if k == 0:
            ctx.tracer.stop()
            counters["traced_boundaries"] = len(clock.times) - 1
            counters["traced_iters"] = int(state.g.iters)
        intervals += clock.intervals_ms
        sr, n_offered = res.serving[cell.name], int(times.size)
        commits += sr.engine.commits
        offered += n_offered
        unanswered = sr.arrived - sr.rejected - sr.shed - sr.completed
        failed += sr.rejected + sr.shed + unanswered
        txn = np.asarray(jax.device_get(state.th.txn))
        txn_total = int(txn.sum())
        numbers.append({
            "unanswered": unanswered,
            "request_ledger_gap": (abs(sr.arrived - n_offered)
                                   + abs(sr.completed - txn_total)),
            "skipped_boundaries": abs(len(clock.times) - 1 - n_bounds)})
        finals.append(calls.keep_final(state, seed, cell.workload.hot_base))
        answers.append({"seed": seed, "hot_base": cell.workload.hot_base,
                        "times": times, "got": _answers(res, cell, txn)})
        del state, res
        k += 1
    wall = time.perf_counter() - t0
    counters.update(window_compiles=calls.window_compiles(before), calls=k,
                    boundaries=len(intervals), commits=commits,
                    boundary_ms_max=max(intervals))
    return calls.Outcome(
        e2e={"served_txn_per_s": commits / wall,
             "boundary_ms_p95": percentile(intervals, 95.0)},
        attempted=offered, failed=failed, finals=finals, numbers=numbers,
        counters=counters, answers=answers)


def _answers(res, cell, txn) -> dict:
    """What one served call answered, in the reference's terms."""
    recs = [{"t1": r["t1"], "commits": r["commits"], "arrived": r["arrived"],
             "rejected": r["rejected"], "completed": r["completed"],
             "qlen": r["qlen"], "in_flight": r["in_flight"],
             "breakdown": tuple(r["breakdown"][b] for b in served_2pl.BINS)}
            for r in res.segments[cell.name]]
    eng = res.serving[cell.name].engine
    return {"records": recs,
            "responses": [round(10 * u) for u in res.responses[cell.name]],
            "commits": eng.commits, "forced_aborts": eng.forced_aborts,
            "txn": txn[:cell.n_threads]}


def compare(cell, outcome) -> list[dict]:
    """Each call of the window against the reference: ``event_mismatch``
    counts the answers that differ (0 on a sound run)."""
    cfg, tr = cell.config, cell.traffic
    wl, T = cfg["workload"], cfg["n_threads"]
    seg, n_bounds = int(tr["seg_ticks"]), int(tr["boundaries"])
    cdf = (reference.zipf_cdf(wl["n_rows"], wl["zipf_s"])
           if wl["kind"] == "zipf" else None)
    out = []
    for a in outcome.answers:
        want = served_2pl.serve_call(
            wl, a["seed"], a["hot_base"], cfg["costs"],
            served_2pl.PROTOCOLS[tr["protocol"]], T, a["times"], seg,
            n_bounds, int(tr["queue_cap_per_thread"]) * T,
            _max_outstanding(seg, tr["rate_per_tick"], T), cdf)
        out.append({"event_mismatch": served_2pl.mismatch(want, a["got"])})
    return out
