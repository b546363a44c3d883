"""Observability-layer tests (DESIGN.md §11): trace-off bit-exactness,
zero-recompile capacity changes, overflow semantics, tick conservation,
and export validity."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.adaptive import FixedPolicy, GovernorCell, run_governed
from repro.core.lock import (CostModel, WorkloadSpec, extract, simulate)
from repro.core.lock import engine as E
from repro.core.lock.workload import hot_migration
from repro.obs import (EV_COMMIT, EV_VICTIM, EV_WAIT_ENTER,
                       check_conservation, events_host, fractions,
                       make_trace, run_traced, simulate_traced, tick_sum,
                       to_chrome_trace, wait_profile)
from repro.obs import trace as obs_trace
from repro.obs.export import _wait_spans
from repro.sweep.runner import MIN_T_BUCKET, _pow2ceil

ZIPF = WorkloadSpec(kind="zipf", txn_len=4, n_rows=512, zipf_s=0.9)
HOT = WorkloadSpec(kind="hotspot_update", txn_len=1, n_rows=512)
PROTOCOLS = ["mysql", "o1", "o2", "group", "bamboo", "brook2pl"]
HORIZON = 60_000


def leaves(tree):
    return jax.tree_util.tree_leaves(tree)


class TestTraceOffParity:
    """trace_on=False must be the stock engine, bit for bit — the whole
    layer is opt-in (ISSUE acceptance gate)."""

    @pytest.mark.parametrize("proto", PROTOCOLS)
    def test_bit_exact_off(self, proto):
        s_ref = simulate(proto, ZIPF, n_threads=24, horizon=HORIZON)
        s_off, tb = simulate_traced(proto, ZIPF, n_threads=24,
                                    horizon=HORIZON, trace_on=False)
        for a, b in zip(leaves(s_off), leaves(s_ref)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert int(tb.n) == 0 and int(tb.dropped) == 0

    def test_bit_exact_even_on(self):
        # tracing only *reads* StepEvents; SimState never depends on the
        # buffer, so even trace_on=True leaves the run unchanged
        s_ref = simulate("mysql", ZIPF, n_threads=24, horizon=HORIZON)
        s_on, _ = simulate_traced("mysql", ZIPF, n_threads=24,
                                  horizon=HORIZON)
        for a, b in zip(leaves(s_on), leaves(s_ref)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestCompileKey:
    def test_cap_on_protocol_share_one_executable(self):
        """Capacity, the on-switch, and the protocol are traced data —
        one (shape, alloc) bucket compiles exactly once."""
        simulate_traced("mysql", ZIPF, n_threads=24, horizon=5_000,
                        cap=4096, alloc=4096)          # warm the bucket
        n0 = obs_trace._run_traced._cache_size()
        for proto in PROTOCOLS:
            for cap, on in [(64, True), (4096, True), (4096, False)]:
                simulate_traced(proto, ZIPF, n_threads=24, horizon=5_000,
                                cap=cap, alloc=4096, trace_on=on)
        assert obs_trace._run_traced._cache_size() == n0

    def test_classic_path_untouched_by_events_refactor(self):
        # the untraced entry points still route through the event-free
        # wrapper: running simulate() must not compile _run_traced
        n0 = obs_trace._run_traced._cache_size()
        simulate("o2", ZIPF, n_threads=24, horizon=5_000)
        assert obs_trace._run_traced._cache_size() == n0


class TestOverflow:
    def test_drops_preserve_prefix(self):
        _, big = simulate_traced("mysql", ZIPF, n_threads=24,
                                 horizon=HORIZON, cap=4096, alloc=4096)
        _, small = simulate_traced("mysql", ZIPF, n_threads=24,
                                   horizon=HORIZON, cap=64, alloc=4096)
        ev_b, ev_s = events_host(big), events_host(small)
        assert ev_b["dropped"] == 0 and ev_b["n"] > 64
        assert ev_s["n"] == 64
        assert ev_s["dropped"] == ev_b["n"] - 64
        for col in ("ts", "tid", "row", "ev"):
            assert np.array_equal(ev_s[col], ev_b[col][:64]), col

    def test_time_ordered(self):
        _, tb = simulate_traced("mysql", ZIPF, n_threads=24,
                                horizon=HORIZON, cap=4096)
        ts = events_host(tb)["ts"]
        assert np.all(np.diff(ts) >= 0)

    def test_commit_events_match_commit_count(self):
        s, tb = simulate_traced("group", ZIPF, n_threads=24,
                                horizon=HORIZON, cap=16_384)
        ev = events_host(tb)
        assert ev["dropped"] == 0
        r = extract("group", 24, s)
        assert int(np.sum(ev["ev"] == EV_COMMIT)) == r.commits

    def test_mysql_zipf_has_deadlock_victims(self):
        _, tb = simulate_traced("mysql", ZIPF, n_threads=24,
                                horizon=HORIZON, cap=16_384)
        ev = events_host(tb)
        assert int(np.sum(ev["ev"] == EV_VICTIM)) >= 1


class TestConservation:
    """sum(TickBreakdown) == padded_T x elapsed ticks, exactly."""

    @pytest.mark.parametrize("proto", PROTOCOLS)
    def test_simulate(self, proto):
        s = simulate(proto, ZIPF, n_threads=24, horizon=HORIZON)
        check_conservation(s, int(s.th.phase.shape[0]))

    def test_with_drain_and_costs(self):
        s = simulate("group", HOT, n_threads=64, horizon=HORIZON,
                     drain=True, costs=CostModel(sync_lat=2_000))
        pad_t = int(s.th.phase.shape[0])
        check_conservation(s, pad_t)
        # drain runs past the horizon; elapsed is whatever now says
        assert tick_sum(s) == pad_t * int(s.g.now)

    def test_aborts(self):
        s = simulate("o2", ZIPF, n_threads=24, horizon=HORIZON,
                     p_abort=0.05)
        check_conservation(s, int(s.th.phase.shape[0]))

    def test_fractions_sum_to_one(self):
        s = simulate("mysql", HOT, n_threads=64, horizon=HORIZON)
        r = extract("mysql", 64, s)
        assert sum(fractions(r.breakdown).values()) == pytest.approx(1.0)

    def test_every_governed_segment_conserves(self):
        """Per-window deltas conserve too (drifting workload, resumable
        segments) — the v3 store rows are balanced books, not just the
        final totals."""
        drift = hot_migration(ZIPF, 4, n_sites=4, period=1)
        res = run_governed(
            [GovernorCell("c", FixedPolicy("mysql"), drift, 12)],
            horizon=48_000, n_segments=4)
        pad_t = _pow2ceil(12, MIN_T_BUCKET)
        segs = res.segments["c"]
        assert len(segs) == 4
        for seg in segs:
            window = seg["t1"] - seg["t0"]
            assert window > 0
            assert sum(seg["breakdown"].values()) == pad_t * window
            assert sum(seg["wait_hist"]) == ZIPF.n_rows
            assert sum(seg["occ_hist"]) == seg["n_hot"]


class TestSnapshotHistograms:
    def test_wait_hist_counts_all_rows(self):
        cfg = E.EngineConfig(
            protocol=E.protocol_params("mysql"), costs=CostModel(),
            workload=ZIPF, n_threads=24, horizon=HORIZON)
        stat, dp = E.split_config(cfg)
        s0 = E.init_state_dyn(stat, dp)
        _, _, snap = run_traced(stat, dp, s0, make_trace(256))
        wait_hist = np.asarray(snap.wait_hist)
        occ_hist = np.asarray(snap.occ_hist)
        assert int(wait_hist.sum()) == ZIPF.n_rows
        assert int(occ_hist.sum()) == int(snap.n_hot)
        # contended zipf: some rows must have non-empty wait queues
        assert int(wait_hist[1:].sum()) > 0

    _EDGES = np.array([0, 1] + [v for k in range(1, 12)
                                for v in (2**k - 1, 2**k, 2**k + 1)])
    _CASES = {
        "edges": _EDGES,
        "clamped": np.array([2**11 + 1, 2**12, 2**20, 2**31 - 1, 3000]),
        "negatives": np.array([-1, -2, -(2**31), 0, 5]),
        "mixed": np.concatenate([np.zeros(40, np.int64), _EDGES,
                                 [-3, 2**13, 7, 7, 1]]),
    }

    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("case", sorted(_CASES))
    def test_bucket_counts_match_bincount(self, case, masked):
        v = jnp.asarray(self._CASES[case], jnp.int32)
        bk = E._q_bucket(v)
        hot = (np.arange(v.shape[0]) % 3 != 1) if masked else None
        got = np.asarray(E._bucket_counts(
            bk, None if hot is None else jnp.asarray(hot)))
        bk_h = np.asarray(bk)
        want = np.bincount(bk_h if hot is None else bk_h[hot],
                           minlength=E.N_QHIST)
        assert got.dtype == np.int32
        assert np.array_equal(got, want)
        if case == "edges" and not masked:
            assert (want > 0).all()           # every bucket is hit

    def test_batched_snapshot_equals_per_lane_and_scatter(self):
        lanes = [("mysql", {}, 0, 30_000), ("group", {"hot_threshold": 1}, 1,
                                             25_000),
                 ("group", {"hot_threshold": 2}, 2, 35_000)]
        stat, dps, s0s, untils = None, [], [], []
        for proto, over, seed, until in lanes:
            cfg = E.EngineConfig(
                protocol=E.protocol_params(proto, **over), costs=CostModel(),
                workload=WorkloadSpec(kind="zipf", txn_len=4, n_rows=512,
                                      zipf_s=0.99, seed=seed),
                n_threads=64, horizon=HORIZON)
            stat, dp = E.split_config(cfg)
            dps.append(dp)
            s0s.append(E.init_state_dyn(stat, dp))
            untils.append(until)
        stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)
        out, snaps = E._run_seg_batch(stat, stack(dps), stack(s0s),
                                      jnp.asarray(untils, jnp.int32))
        for i, (dp, s0, until) in enumerate(zip(dps, s0s, untils)):
            s, snap = E._run_seg_dyn(stat, dp, s0,
                                     jnp.asarray(until, jnp.int32))
            for f in ("wait_hist", "occ_hist"):
                assert np.array_equal(np.asarray(getattr(snaps, f)[i]),
                                      np.asarray(getattr(snap, f)))
            # the scatter-add form the histograms replaced
            d = E._derive(stat, dp, s.th, s.rows)
            z = jnp.zeros((E.N_QHIST,), jnp.int32)
            assert np.array_equal(
                np.asarray(snap.wait_hist),
                np.asarray(z.at[E._q_bucket(d.n_wait)].add(1)))
            assert np.array_equal(
                np.asarray(snap.occ_hist),
                np.asarray(z.at[E._q_bucket(d.n_live)].add(
                    jnp.where(s.rows.hot, 1, 0))))
            assert int(np.asarray(snap.wait_hist)[1:].sum()) > 0
        # the group lanes promote rows, so occ_hist is not trivially zero
        assert int(np.asarray(snaps.occ_hist)[1:].sum()) > 0


class TestExport:
    def _events(self):
        _, tb = simulate_traced("mysql", ZIPF, n_threads=24,
                                horizon=HORIZON, cap=16_384)
        return events_host(tb)

    def test_chrome_trace_valid_json(self):
        ev = self._events()
        doc = to_chrome_trace(ev, label="test")
        doc2 = json.loads(json.dumps(doc))    # round-trips
        assert doc2["traceEvents"]
        for e in doc2["traceEvents"]:
            assert e["ph"] in ("M", "X", "i")
            if e["ph"] == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0
        assert doc2["otherData"]["dropped"] == 0

    def test_wait_spans_cover_wait_enters(self):
        ev = self._events()
        n_spans = sum(1 for _ in _wait_spans(ev))
        assert n_spans == int(np.sum(ev["ev"] == EV_WAIT_ENTER))

    def test_wait_profile_report(self):
        txt = wait_profile(self._events(), top_k=5)
        lines = txt.splitlines()
        assert lines[0].startswith("# wait profile")
        header = lines[1].split(",")
        assert header[0] == "row" and "deadlock_victim" in header
        assert len(lines) <= 2 + 5

    def test_wait_profile_warns_on_drop(self):
        _, tb = simulate_traced("mysql", ZIPF, n_threads=24,
                                horizon=HORIZON, cap=64, alloc=4096)
        assert "WARNING" in wait_profile(tb)
