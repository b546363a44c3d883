"""Serving-layer tests: differential parity against the closed loop,
analytic M/M/c validation (Thomasian, arXiv:2404.02276), open-system
invariants (property tests) and compile discipline.

Parity standard (same bar test_sweep.py holds the sweep substrate to):
with a saturating schedule and unbinding credit quotas, the serving path
IS the segmented closed loop — every state leaf must match a single-shot
run of the same padded config bit-for-bit, except the diagnostic
``Globals.iters``, which a segment boundary may legitimately split
(0 <= open - ref <= n_segments - 1, the run_segment contract).
"""
import dataclasses
import os

import numpy as np
import pytest
import jax

from repro.core.lock import engine as E
from repro.core.lock import (CostModel, WorkloadSpec, extract, simulate,
                             protocol_params)
from repro.serving import (ArrivalSchedule, ServeCell, bursty, flash_crowd,
                           poisson, predicted_response_ticks,
                           predicted_util, saturating, serve, service_ticks,
                           uniform)

SEED = 11


# ---------------------------------------------------------------------------
# arrival schedules
# ---------------------------------------------------------------------------

class TestArrivals:
    def test_poisson_rate_and_determinism(self):
        a = poisson(0.01, 400_000, seed=SEED)
        b = poisson(0.01, 400_000, seed=SEED)
        assert np.array_equal(a.times, b.times)     # seeded => bit-stable
        assert a.times.dtype == np.int64
        assert (np.diff(a.times) >= 0).all()
        assert 0 <= a.times[0] and a.times[-1] < 400_000
        # ~4000 expected arrivals; Poisson sd ~63 — 5 sigma
        assert abs(a.n - 4000) < 320
        assert a.offered_tps == pytest.approx(a.n * 1e7 / 400_000)

    def test_bursty_and_flash_crowd_modulate(self):
        b = bursty(0.001, 0.02, 400_000, period=100_000, duty=0.25,
                   seed=SEED)
        in_burst = (b.times % 100_000) < 25_000
        # burst quarters carry ~20x the base rate
        assert in_burst.sum() > 3 * (~in_burst).sum()
        f = flash_crowd(0.001, 0.02, 400_000, at=0.5, spike_frac=0.25,
                        seed=SEED)
        spike = (f.times >= 200_000) & (f.times < 300_000)
        assert spike.sum() > 2 * (~spike).sum()

    def test_uniform_and_saturating(self):
        u = uniform(0.001, 100_000)
        assert u.n == 100 and np.diff(u.times).min() == 1000
        s = saturating(500, 100_000)
        assert s.n == 500 and s.times.max() == 0

    def test_schedule_validation(self):
        with pytest.raises(AssertionError):
            ArrivalSchedule("bad", np.array([5, 3]), 10)
        with pytest.raises(AssertionError):
            ArrivalSchedule("bad", np.array([3, 50]), 10)


# ---------------------------------------------------------------------------
# differential parity: open system == closed loop when saturated
# ---------------------------------------------------------------------------

W_PARITY = WorkloadSpec(kind="zipf", txn_len=4, n_rows=1024, zipf_s=0.9)
T_PARITY, H_PARITY, SEG_PARITY = 8, 120_000, 20_000


def _closed_loop_state(preset: str, pad_t: int):
    """Single-shot reference at the serving layer's padded shape."""
    cfg = E.EngineConfig(protocol=protocol_params(preset),
                         costs=CostModel(), workload=W_PARITY,
                         n_threads=T_PARITY, horizon=H_PARITY)
    stat, dp = E.split_config(cfg, pad_threads=pad_t)
    return E._run_dyn(stat, dp, E.init_state_dyn(stat, dp))


class TestSaturatingParity:
    @pytest.fixture(scope="class")
    def served(self):
        # enough requests that the queue outlives the horizon; per-slot
        # credit high enough that the quota never binds => the device
        # must replay the closed loop exactly
        sched = saturating(30_000, H_PARITY)
        cells = [ServeCell(name=p, schedule=sched, workload=W_PARITY,
                           n_threads=T_PARITY, preset=p, admission="wait",
                           max_outstanding=30_000)
                 for p in ("mysql", "group")]
        return serve(cells, seg_ticks=SEG_PARITY, return_states=True)

    @pytest.mark.parametrize("preset", ["mysql", "group"])
    def test_every_state_leaf_bitexact(self, served, preset):
        n_seg = H_PARITY // SEG_PARITY
        s_open = served.states[preset]
        s_ref = _closed_loop_state(preset, 64)
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(s_ref)[0]]
        o = jax.device_get(jax.tree.leaves(s_open))
        r = jax.device_get(jax.tree.leaves(s_ref))
        for path, a, b in zip(paths, o, r):
            if path.endswith(".iters"):
                d = int(a) - int(b)
                assert 0 <= d <= n_seg - 1, (path, d)
            else:
                assert np.array_equal(a, b), path

    @pytest.mark.parametrize("preset", ["mysql", "group"])
    def test_metrics_match_simulate(self, served, preset):
        """Extracted metrics equal plain simulate()'s, field for field
        (iters excepted per the segment contract)."""
        ref = extract(preset, T_PARITY,
                      simulate(preset, W_PARITY, T_PARITY,
                               horizon=H_PARITY))
        got = served.metrics[preset]
        for f in ("commits", "user_aborts", "forced_aborts", "lock_ops",
                  "dd_ticks", "tps", "mean_latency_us", "p95_latency_us",
                  "abort_rate", "lock_wait_frac", "cpu_util"):
            assert getattr(got, f) == getattr(ref, f), (preset, f)
        assert 0 <= got.iters - ref.iters <= H_PARITY // SEG_PARITY - 1

    def test_serving_counts_match_engine(self, served):
        """Responses are txn completions: completed == commits (p_abort=0)
        and the quota never rejected or queued out anything."""
        for p in ("mysql", "group"):
            s = served.serving[p]
            assert s.completed == served.metrics[p].commits
            assert s.rejected == 0 and s.shed == 0
            assert s.arrived == 30_000
            assert s.completed + s.in_flight_end + s.qlen_end == 30_000

    def test_single_compile_for_both_protocols(self, served):
        assert served.n_compiles <= 1


class TestCompileDiscipline:
    def test_second_run_compiles_nothing(self):
        """Repeated serving runs (fresh schedules, same shapes) must hit
        the segment executable cache — the acceptance criterion."""
        def run(seed):
            cells = [ServeCell(name=f"c{seed}", workload=W_PARITY,
                               schedule=poisson(0.003, 60_000, seed=seed),
                               n_threads=T_PARITY, preset="mysql",
                               max_outstanding=64, admission="wait")]
            return serve(cells, seg_ticks=15_000)
        run(1)                          # warm (may compile)
        res2 = run(2)
        assert res2.n_compiles == 0


# ---------------------------------------------------------------------------
# analytic validation (Thomasian M/M/c, low contention)
# ---------------------------------------------------------------------------

W_MMC = WorkloadSpec(kind="uniform", txn_len=4, n_rows=65_536,
                     write_ratio=0.5)
T_MMC, H_MMC, SEG_MMC = 8, 120_000, 500
TOL = 0.15


def _mmc_measure(rhos):
    costs = CostModel()
    cap = T_MMC / service_ticks(W_MMC, costs, "mysql")  # arrivals/tick
    cells = [ServeCell(name=f"rho{r}", workload=W_MMC, n_threads=T_MMC,
                       schedule=poisson(r * cap, H_MMC, seed=7),
                       preset="mysql", admission="wait",
                       max_outstanding=1_000)
             for r in rhos]
    res = serve(cells, seg_ticks=SEG_MMC, chunk_size=len(cells))
    out = []
    for r in rhos:
        s = res.serving[f"rho{r}"]
        # the boundary quantization correction (DESIGN.md §10): dispatch
        # waits mean seg/2 after arrival, observation rounds up mean
        # seg/2 after completion
        pred = predicted_response_ticks(r * cap, W_MMC, costs,
                                        T_MMC, "mysql") + SEG_MMC
        pred_u = predicted_util(r * cap, W_MMC, costs, T_MMC, "mysql")
        out.append((r, s.mean_resp_us * 10.0, pred, s.utilization, pred_u,
                    s.completed))
    return out


class TestAnalyticValidation:
    def test_mmc_below_knee(self):
        """Measured mean response and utilization within ±15% of the
        M/M/c prediction at 3 offered loads below the knee."""
        rows = _mmc_measure((0.2, 0.4, 0.6))
        for rho, meas, pred, util, pred_u, n in rows:
            assert n > 300, (rho, n)    # enough completions to average
            assert meas == pytest.approx(pred, rel=TOL), (rho, meas, pred)
            assert util == pytest.approx(pred_u, rel=TOL), (rho, util)

    @pytest.mark.skipif(not os.environ.get("REPRO_SERVING_FULL"),
                        reason="full analytic curve: REPRO_SERVING_FULL=1")
    def test_mmc_full_curve(self):
        rows = _mmc_measure((0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8))
        for rho, meas, pred, util, pred_u, _ in rows:
            assert meas == pytest.approx(pred, rel=TOL), (rho, meas, pred)
            assert util == pytest.approx(pred_u, rel=TOL), (rho, util)


# ---------------------------------------------------------------------------
# admission control semantics
# ---------------------------------------------------------------------------

W_SMALL = WorkloadSpec(kind="uniform", txn_len=2, n_rows=512,
                       write_ratio=1.0)


def _overloaded(admission, cap=8):
    cells = [ServeCell(name="x", schedule=saturating(2_000, 20_000),
                       workload=W_SMALL, n_threads=4, preset="o2",
                       queue_cap=cap, admission=admission,
                       max_outstanding=2)]
    return serve(cells, seg_ticks=5_000).serving["x"]


class TestAdmission:
    def test_reject_drops_newcomers(self):
        s = _overloaded("reject")
        assert s.rejected > 0 and s.shed == 0
        assert s.qlen_end <= 8

    def test_shed_drops_oldest(self):
        s = _overloaded("shed")
        assert s.shed > 0 and s.rejected == 0
        assert s.qlen_end <= 8

    def test_wait_is_unbounded(self):
        s = _overloaded("wait")
        assert s.rejected == 0 and s.shed == 0
        assert s.qlen_end > 8                   # cap ignored
        # conservation still holds
        assert s.arrived == s.completed + s.in_flight_end + s.qlen_end


# ---------------------------------------------------------------------------
# property tests (hypothesis)
# ---------------------------------------------------------------------------

class TestProperties:
    """Open-system invariants over drawn schedules and admission knobs."""

    @pytest.fixture(autouse=True)
    def _hyp(self):
        pytest.importorskip(
            "hypothesis",
            reason="property tests need hypothesis (requirements-dev)")

    def test_conservation_and_queue_bound_at_every_boundary(self):
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=8, deadline=None)
        @given(seed=st.integers(0, 2**16), rate=st.floats(0.001, 0.05),
               cap=st.integers(2, 32),
               admission=st.sampled_from(["reject", "shed"]),
               mo=st.integers(1, 8))
        def prop(seed, rate, cap, admission, mo):
            cells = [ServeCell(name="p", workload=W_SMALL, n_threads=4,
                               schedule=poisson(rate, 20_000, seed=seed),
                               preset="o2", queue_cap=cap,
                               admission=admission, max_outstanding=mo)]
            res = serve(cells, seg_ticks=5_000)
            cum_arr = cum_rej = cum_shed = cum_done = 0
            for rec in res.segments["p"]:
                cum_arr += rec["arrived"]
                cum_rej += rec["rejected"]
                cum_shed += rec["shed"]
                cum_done += rec["completed"]
                # queue length never exceeds the backpressure cap
                assert rec["qlen"] <= cap
                # admitted = completed + rejected(+shed) + queued +
                # in-flight, at EVERY boundary
                assert cum_arr == (cum_rej + cum_shed + cum_done
                                   + rec["qlen"] + rec["in_flight"])
            s = res.serving["p"]
            assert (cum_arr, cum_rej, cum_shed, cum_done) == (
                s.arrived, s.rejected, s.shed, s.completed)

        prop()

    def test_percentile_ordering_and_load_monotonicity(self):
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=4, deadline=None)
        @given(seed=st.integers(0, 2**16))
        def prop(seed):
            # fixed protocol, rising offered load across the knee.
            # Responses are observed at boundaries, so seg_ticks is their
            # resolution: at 8_000 the rise from 0.3 to 1.0 of capacity
            # (tens of us) drowned in quantization noise for about one
            # seed in seven; at 2_000 it stands clear of it.
            cap = 4 / service_ticks(W_SMALL, CostModel(), "o2")
            cells = [ServeCell(name=f"l{i}", workload=W_SMALL,
                               n_threads=4, preset="o2", admission="wait",
                               schedule=poisson(f * cap, 40_000,
                                                seed=seed),
                               max_outstanding=50)
                     for i, f in enumerate((0.3, 1.0, 3.0))]
            res = serve(cells, seg_ticks=2_000)
            means = []
            for i in range(3):
                s = res.serving[f"l{i}"]
                assert s.p50_us <= s.p99_us <= s.p999_us <= s.max_us
                means.append(s.mean_resp_us)
            # latencies monotone non-decreasing in offered load
            assert means[0] <= means[1] <= means[2]

        prop()


# ---------------------------------------------------------------------------
# device-histogram percentiles vs host response lists (obs layer)
# ---------------------------------------------------------------------------

class TestDevicePercentiles:
    """ServingResult p50/p99/p999 now come from the engine's log-bucket
    response histogram, not a host-side list. ``keep_responses=True``
    retains the old per-request list purely so this test can check the
    two agree to within the histogram's bucket resolution."""

    def test_hist_percentiles_match_host_responses(self):
        # ~70% of M/M/c capacity on the contention-free workload: busy
        # enough for a wide queueing-delay spread, light enough that most
        # arrivals complete inside the horizon (a contended workload here
        # would collapse and leave too few samples for p999)
        rate = 0.7 * 8 / service_ticks(W_MMC, CostModel(), "o2")
        sched = poisson(rate, 120_000, seed=SEED)
        cells = [ServeCell(name="x", schedule=sched, workload=W_MMC,
                           n_threads=8, preset="o2", admission="wait",
                           max_outstanding=5_000)]
        res = serve(cells, seg_ticks=20_000, keep_responses=True)
        s = res.serving["x"]
        rs = np.sort(np.asarray(res.responses["x"]))
        assert len(rs) == s.completed > 100
        assert s.max_us == pytest.approx(rs[-1])
        # log buckets are base-1.3 wide and report the geometric
        # midpoint, so the device estimate sits within ~sqrt(1.3) of the
        # exact order statistic (inverted CDF), plus the -1 tick offset
        # of the smallest buckets
        for q, got in ((0.50, s.p50_us), (0.99, s.p99_us),
                       (0.999, s.p999_us)):
            k = min(int(np.ceil(q * len(rs))) - 1, len(rs) - 1)
            want = rs[max(k, 0)]
            assert want / 1.35 - 0.5 <= got <= want * 1.35 + 0.5, (
                q, got, want)

    def test_keep_responses_off_by_default(self):
        rate = 0.5 * 4 / service_ticks(W_MMC, CostModel(), "o2")
        cells = [ServeCell(name="x", schedule=poisson(rate, 30_000,
                                                      seed=SEED),
                           workload=W_MMC, n_threads=4, preset="o2",
                           admission="wait", max_outstanding=500)]
        res = serve(cells, seg_ticks=10_000)
        assert res.responses == {}


# ---------------------------------------------------------------------------
# governed serving
# ---------------------------------------------------------------------------

class TestGovernedServing:
    def test_policy_switches_under_open_load(self):
        from repro.adaptive import QueueRulePolicy
        hot = WorkloadSpec(kind="hotspot_update", txn_len=2, n_rows=2048)
        cells = [ServeCell(name="gov", schedule=saturating(4_000, 60_000),
                           workload=hot, n_threads=32, preset="o2",
                           policy=QueueRulePolicy(), admission="wait",
                           max_outstanding=200)]
        res = serve(cells, seg_ticks=10_000)
        presets = [r["preset"] for r in res.segments["gov"]]
        # the rule must promote the saturated hotspot to group locking
        assert "group" in presets
        s = res.serving["gov"]
        assert s.completed == res.metrics["gov"].commits

    def test_resolver_free_switch_rejected(self):
        from repro.adaptive.governor import Policy

        class BadPolicy(Policy):
            name = "bad"

            def decide(self, k, history):
                return "mysql" if k == 0 else "brook2pl"

        cells = [ServeCell(name="bad", workload=W_SMALL, n_threads=4,
                           schedule=saturating(500, 20_000),
                           preset="mysql", policy=BadPolicy(),
                           admission="wait", max_outstanding=200)]
        with pytest.raises(ValueError, match="resolver-free"):
            serve(cells, seg_ticks=5_000)


# ---------------------------------------------------------------------------
# engine parameters: one build per distinct lane config per call
# ---------------------------------------------------------------------------

@pytest.fixture
def count_builds(monkeypatch):
    """Count ``split_config`` calls by the config each one built."""
    real = E.split_config
    built = []

    def counting(cfg, *args, **kwargs):
        built.append(cfg)
        return real(cfg, *args, **kwargs)

    monkeypatch.setattr(E, "split_config", counting)
    return built


class TestParamMemo:
    def test_one_cell_builds_once(self, count_builds):
        cells = [ServeCell(name="one",
                           schedule=poisson(0.002, 30_000, seed=SEED),
                           workload=W_SMALL, n_threads=4, preset="mysql",
                           admission="wait", max_outstanding=8)]
        res = serve(cells, seg_ticks=5_000)
        assert len(res.segments["one"]) == 6
        assert len(count_builds) == 1

    def test_policy_builds_each_preset_once(self, count_builds):
        from repro.adaptive import QueueRulePolicy
        hot = WorkloadSpec(kind="hotspot_update", txn_len=2, n_rows=2048)
        cells = [ServeCell(name="gov", schedule=saturating(4_000, 60_000),
                           workload=hot, n_threads=32, preset="o2",
                           policy=QueueRulePolicy(), admission="wait",
                           max_outstanding=200)]
        res = serve(cells, seg_ticks=10_000)
        presets = [r["preset"] for r in res.segments["gov"]]
        assert set(presets) == {"o2", "group"}
        assert len(count_builds) == 2
        assert len(set(count_builds)) == 2

    def test_bucket_mates_build_once_each(self, count_builds):
        cells = [ServeCell(name=f"c{i}",
                           schedule=poisson(0.002, 30_000, seed=i),
                           workload=dataclasses.replace(W_SMALL, seed=i),
                           n_threads=4, preset="mysql", admission="wait",
                           max_outstanding=8) for i in range(2)]
        res = serve(cells, seg_ticks=5_000)
        assert len(res.buckets) == 1 and res.buckets[0].n_points == 2
        assert len(count_builds) == 2
        assert {cfg.workload.seed for cfg in count_builds} == {0, 1}

    def test_reused_params_serve_what_fresh_ones_do(self, count_builds,
                                                    monkeypatch):
        """A run that builds anew at every boundary (each lookup gets a
        distinct key: ``EngineConfig.seed``, which ``split_config`` does
        not read) answers bit-for-bit what the memoised run answers."""
        from repro.serving import runner

        def run():
            cells = [ServeCell(name="p",
                               schedule=poisson(0.003, 30_000, seed=3),
                               workload=W_PARITY, n_threads=8,
                               preset="mysql", admission="wait",
                               max_outstanding=8)]
            return serve(cells, seg_ticks=5_000, return_states=True,
                         keep_responses=True)

        memo = run()
        assert len(count_builds) == 1
        real, keys = runner._cell_config, iter(range(1, 10**6))
        monkeypatch.setattr(
            runner, "_cell_config",
            lambda *a: dataclasses.replace(real(*a), seed=next(keys)))
        fresh = run()
        # boundary 0 builds twice (initial state, then the segment)
        assert len(count_builds) == 1 + len(fresh.segments["p"]) + 1
        assert memo.segments == fresh.segments
        assert memo.responses == fresh.responses
        for a, b in zip(jax.tree_util.tree_leaves(memo.states["p"]),
                        jax.tree_util.tree_leaves(fresh.states["p"])):
            assert np.array_equal(np.asarray(a), np.asarray(b))
