"""Adaptive-governor subsystem tests: segmented-execution parity (an
N-segment run with constant params matches single-shot ``simulate()``
bit-for-bit, state and metrics, modulo the diagnostic loop counter),
zero-recompile protocol/workload switching (compile counter), drift
schedules, governor policies, governed runs, and the v2 results store."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lock_matrix import SHAPES, each_pair
from repro.adaptive import (DEFAULT_ARMS, EpsilonGreedyPolicy, FixedPolicy,
                            GovernorCell, Policy, QueueRulePolicy,
                            SegmentRecord, preset_timeline, run_governed)
from repro.core.lock import (CostModel, EngineConfig, WorkloadSpec,
                             extract, flash_crowd, hot_migration,
                             protocol_params, simulate, skew_ramp,
                             split_config, stationary)
from repro.core.lock import engine as E
from repro.core.lock.metrics import SimResult, delta_globals, extract_globals
from repro.core.lock.workload import DriftSchedule, gen_txn
from repro.sweep import load_results, save_results, summarize

ZIPF = WorkloadSpec(kind="zipf", txn_len=2, n_rows=256, zipf_s=0.9)
HORIZON = 30_000

METRIC_FIELDS = ("commits", "user_aborts", "forced_aborts", "lock_ops",
                 "tps", "mean_latency_us", "p95_latency_us", "abort_rate",
                 "lock_wait_frac", "cpu_util")


def run_segmented(cfg, n_seg, pad_threads=None, pad_len=None):
    stat, dp = split_config(cfg, pad_threads=pad_threads, pad_len=pad_len)
    s = E.init_state_dyn(stat, dp)
    for k in range(1, n_seg + 1):
        s, snap = E.run_segment(stat, dp, s, cfg.horizon * k // n_seg)
    return stat, s, snap


class TestSegmentedParity:
    @each_pair
    def test_nseg_bitexact_vs_single_shot(self, proto, kind):
        """Constant-dp segmented run == simulate() in EVERY state leaf
        and metric; only Globals.iters may grow (<= one per boundary)."""
        wl, n_threads = SHAPES[kind]
        cfg = EngineConfig(protocol=protocol_params(proto),
                           costs=CostModel(), workload=wl,
                           n_threads=n_threads, horizon=HORIZON)
        stat, dp = split_config(cfg)
        ref = E._run_dyn(stat, dp, E.init_state_dyn(stat, dp))
        n_seg = 5
        _, seg, _ = run_segmented(cfg, n_seg)
        ref_l = jax.tree.leaves(ref)
        seg_l = jax.tree.leaves(seg)
        iters_ref, iters_seg = int(ref.g.iters), int(seg.g.iters)
        mism = [i for i, (a, b) in enumerate(zip(ref_l, seg_l))
                if not bool((np.asarray(a) == np.asarray(b)).all())]
        # the only tolerated mismatch is the iters leaf
        iters_idx = [i for i, x in enumerate(jax.tree.leaves(ref))
                     if x is ref.g.iters]
        assert mism in ([], iters_idx), mism
        assert 0 <= iters_seg - iters_ref <= n_seg - 1

    def test_group_commit_pipeline_parity(self):
        """Regression: the group-commit queue drains one member per loop
        iteration, so splitting a BUSY step at a segment boundary used to
        accelerate the CWAIT->COMMIT pipeline (caught in review at
        group/fit/T=128: tstart/wstart/wait_ticks/lat_sum drifted).
        Boundaries must only ever split idle windows."""
        wl = WorkloadSpec(kind="fit", txn_len=2, n_rows=4096, n_hot=1)
        cfg = EngineConfig(protocol=protocol_params("group"),
                           costs=CostModel(), workload=wl,
                           n_threads=128, horizon=12_000)
        stat, dp = split_config(cfg)
        ref = E._run_dyn(stat, dp, E.init_state_dyn(stat, dp))
        _, seg, _ = run_segmented(cfg, 12)
        for grp, a, b in (("th", ref.th, seg.th), ("rows", ref.rows,
                          seg.rows), ("g", ref.g, seg.g)):
            for n in a._fields:
                if n == "iters":
                    continue
                assert (np.asarray(getattr(a, n))
                        == np.asarray(getattr(b, n))).all(), f"{grp}.{n}"

    def test_padded_parity_metrics(self):
        """Segments at a padded shape (threads AND op slots) produce the
        same metrics as the unpadded single-shot run."""
        cfg = EngineConfig(protocol=protocol_params("mysql"),
                           costs=CostModel(), workload=ZIPF,
                           n_threads=12, horizon=HORIZON, p_abort=0.05)
        _, seg, _ = run_segmented(cfg, 3, pad_threads=64, pad_len=4)
        got = extract_globals("mysql", 12, jax.device_get(seg.g))
        ref = extract("mysql", 12,
                      simulate("mysql", ZIPF, n_threads=12, horizon=HORIZON,
                               p_abort=0.05))
        for f in METRIC_FIELDS:
            assert getattr(got, f) == getattr(ref, f), f

    def test_segments_end_exactly_at_boundary(self):
        """A stalled system must pause AT the boundary (no idle-jump past
        it) so a governor can still act — zipf s0.9 multi-row writes
        deadlock-stall detection-free o2 within the horizon."""
        wl = dataclasses.replace(ZIPF, txn_len=4)
        cfg = EngineConfig(protocol=protocol_params("o2"),
                           costs=CostModel(), workload=wl,
                           n_threads=16, horizon=40_000)
        stat, dp = split_config(cfg)
        s = E.init_state_dyn(stat, dp)
        for until in (10_000, 20_000, 30_000):
            s, _ = E.run_segment(stat, dp, s, until)
            assert int(s.g.now) <= until
        # resumable: switching to a detection protocol unsticks the stall
        _, dp2 = split_config(dataclasses.replace(
            cfg, protocol=protocol_params("mysql")))
        c0 = int(s.g.commits)
        s, _ = E.run_segment(stat, dp2, s, 40_000)
        assert int(s.g.commits) > c0

    def test_delta_globals_splits_counters(self):
        cfg = EngineConfig(protocol=protocol_params("group"),
                           costs=CostModel(), workload=ZIPF,
                           n_threads=8, horizon=HORIZON)
        stat, dp = split_config(cfg)
        s = E.init_state_dyn(stat, dp)
        g0 = jax.device_get(s.g)
        s, _ = E.run_segment(stat, dp, s, HORIZON // 2)
        g1 = jax.device_get(s.g)
        s, _ = E.run_segment(stat, dp, s, HORIZON)
        g2 = jax.device_get(s.g)
        d01, d12 = delta_globals(g0, g1), delta_globals(g1, g2)
        assert int(d01.commits) + int(d12.commits) == int(g2.commits)
        assert int(d01.now) + int(d12.now) == int(g2.now)
        assert (np.asarray(d01.hist) + np.asarray(d12.hist)
                == np.asarray(g2.hist)).all()


class TestCompileCounter:
    def test_switches_cost_zero_recompiles(self):
        """Segment boundaries, protocol switches, workload drift, and new
        cells at the same shape all reuse ONE compiled program."""
        wl = dataclasses.replace(ZIPF, n_rows=251)    # unique shape: cold
        cfg = EngineConfig(protocol=protocol_params("o2"),
                           costs=CostModel(), workload=wl,
                           n_threads=8, horizon=20_000)
        stat, dp = split_config(cfg)
        n0 = E._run_seg_dyn._cache_size()
        s = E.init_state_dyn(stat, dp)
        s, _ = E.run_segment(stat, dp, s, 5_000)
        assert E._run_seg_dyn._cache_size() - n0 == 1
        for proto, zs, hb, until in (("mysql", 0.3, 0, 10_000),
                                     ("group", 1.1, 99, 15_000),
                                     ("bamboo", 0.7, 7, 20_000)):
            w2 = dataclasses.replace(wl, zipf_s=zs, hot_base=hb)
            _, dp2 = split_config(dataclasses.replace(
                cfg, protocol=protocol_params(proto), workload=w2))
            s, _ = E.run_segment(stat, dp2, s, until)
        s2 = E.init_state_dyn(stat, dp)          # a fresh cell, same shape
        E.run_segment(stat, dp, s2, 9_999)
        assert E._run_seg_dyn._cache_size() - n0 == 1


class TestDriftSchedules:
    def test_builders_shapes_and_compile_key(self):
        base = WorkloadSpec(kind="zipf", txn_len=2, n_rows=512)
        for ds in (stationary(base, 6), hot_migration(base, 6),
                   skew_ramp(base, 6), flash_crowd(base, 6, skew_hi=1.0)):
            assert ds.n_segments == 6
            keys = {(s.kind, s.n_rows, s.txn_len) for s in ds.specs}
            assert len(keys) == 1                 # stable compile key
        assert ds.spec(99) == ds.specs[-1]        # clamped

    def test_kind_change_rejected(self):
        a = WorkloadSpec(kind="zipf", txn_len=2)
        b = WorkloadSpec(kind="uniform", txn_len=2)
        with pytest.raises(AssertionError, match="compile key"):
            DriftSchedule("bad", (a, b))

    def test_hot_migration_moves_the_hot_row(self):
        base = WorkloadSpec(kind="hotspot_update", txn_len=2, n_rows=1024)
        ds = hot_migration(base, 8, n_sites=4, period=2)
        anchors = [s.hot_base for s in ds.specs]
        assert anchors == [0, 0, 256, 256, 512, 512, 768, 768]
        tids = jnp.arange(4, dtype=jnp.int32)
        ctr = jnp.zeros(4, jnp.int32)
        keys, _, _, _, _ = gen_txn(ds.spec(2), tids, ctr)
        assert (np.asarray(keys[:, 0]) == 256).all()   # op 0 hits the site
        keys0, _, _, _, _ = gen_txn(ds.spec(0), tids, ctr)
        assert (np.asarray(keys0[:, 0]) == 0).all()

    def test_skew_ramp_endpoints(self):
        ds = skew_ramp(WorkloadSpec(kind="zipf"), 5, lo=0.2, hi=1.0)
        assert ds.specs[0].zipf_s == 0.2 and ds.specs[-1].zipf_s == 1.0

    def test_flash_crowd_step(self):
        ds = flash_crowd(WorkloadSpec(kind="hotspot_mix"), 8, at=0.5,
                         write_lo=0.1, write_hi=0.9, skew_hi=1.2)
        wr = [s.write_ratio for s in ds.specs]
        assert wr == [0.1] * 4 + [0.9] * 4
        assert ds.specs[-1].zipf_s == 1.2 and ds.specs[0].zipf_s == 0.7


def _rec(index=0, preset="o2", tps=1e6, max_qlen=0, n_waiting=0,
         lock_wait_frac=0.0, n_threads=64):
    m = SimResult(protocol=preset, n_threads=n_threads, commits=1000,
                  user_aborts=0, forced_aborts=0, lock_ops=0,
                  sim_seconds=0.01, tps=tps, mean_latency_us=1.0,
                  p95_latency_us=1.0, p99_latency_us=1.0,
                  lock_wait_frac=lock_wait_frac, cpu_util=0.5,
                  abort_rate=0.0, iters=10)
    return SegmentRecord(index=index, t0=0, t1=1000, preset=preset,
                         metrics=m, max_qlen=max_qlen, n_hot=0,
                         n_live=0, n_waiting=n_waiting)


class TestPolicies:
    def test_fixed(self):
        p = FixedPolicy("group")
        p.reset(64)
        assert p.decide(0, []) == "group"
        assert p.decide(5, [_rec()]) == "group"

    def test_rule_branches(self):
        p = QueueRulePolicy()
        p.reset(64)
        assert p.decide(0, []) == "o2"
        # concentrated deep queue -> group locking (hotspot)
        assert p.decide(1, [_rec(max_qlen=60, n_waiting=62)]) == "group"
        # long but dispersed queues + most threads waiting -> detection
        assert p.decide(1, [_rec(max_qlen=25, n_waiting=60)]) == "mysql"
        # calm -> cheapest path
        assert p.decide(1, [_rec(preset="mysql", max_qlen=1, n_waiting=2,
                                 lock_wait_frac=0.01)]) == "o2"
        # ambiguous middle keeps the incumbent (hysteresis)
        assert p.decide(1, [_rec(preset="mysql", max_qlen=3, n_waiting=12,
                                 lock_wait_frac=0.2)]) == "mysql"

    def test_greedy_bootstrap_then_exploit(self):
        p = EpsilonGreedyPolicy(arms=DEFAULT_ARMS)
        p.reset(64)
        hist = []
        for k, (arm, tps) in enumerate(zip(DEFAULT_ARMS, (3e6, 2e6, 1e6))):
            got = p.decide(k, hist)
            assert got == arm                     # bootstrap in arm order
            hist.append(_rec(index=k, preset=arm, tps=tps))
        assert p.decide(3, hist) == "o2"          # exploit the best

    def test_greedy_drop_taints_family_and_reprobes(self):
        """Drive the policy segment-by-segment like the runner does: an
        o2 collapse must re-probe mysql but NOT family-mate group (which
        inherits the collapsed estimate)."""
        p = EpsilonGreedyPolicy(arms=DEFAULT_ARMS, drop_frac=0.5)
        p.reset(64)
        hist = []
        # (observed tps for the preset the policy chose at each step)
        script = {"o2": [3e6, 4e6, 10_000.0],
                  "group": [2.5e6], "mysql": [2e6, 1.5e6, 1.5e6]}
        chosen = []
        for k in range(7):
            arm = p.decide(k, hist)
            chosen.append(arm)
            hist.append(_rec(index=k, preset=arm,
                             tps=script[arm].pop(0)))
        # bootstrap o2/group/mysql, exploit o2, collapse, re-probe mysql,
        # exploit mysql — group is never probed again after the taint
        assert chosen == ["o2", "group", "mysql", "o2", "o2",
                          "mysql", "mysql"]
        assert p.est["group"] == 10_000.0


class TestRunGoverned:
    def test_fixed_stationary_cell_matches_simulate(self):
        """The governed path with a never-switching policy and stationary
        drift is the plain simulation, bit-for-bit (metrics)."""
        drift = stationary(ZIPF, 4)
        res = run_governed(
            [GovernorCell("cell", FixedPolicy("group"), drift, 8)],
            horizon=HORIZON, n_segments=4)
        ref = extract("group", 8,
                      simulate("group", ZIPF, n_threads=8, horizon=HORIZON))
        for f in METRIC_FIELDS:
            assert getattr(res["cell"], f) == getattr(ref, f), f

    def test_records_and_totals_consistent(self):
        # unique n_rows -> cold cache -> the compile count is exact
        drift = skew_ramp(dataclasses.replace(ZIPF, n_rows=257), 4,
                          lo=0.3, hi=1.1)
        res = run_governed(
            [GovernorCell("a", QueueRulePolicy(), drift, 8),
             GovernorCell("b", FixedPolicy("mysql"), drift, 8)],
            horizon=HORIZON, n_segments=4)
        assert res.n_compiles == 1                # one bucket, one program
        for name in ("a", "b"):
            segs = res.segments[name]
            assert len(segs) == 4
            # busy cells pause at their first event past each boundary;
            # nothing ever runs past the horizon
            for s, bound in zip(segs, (HORIZON * k // 4 for k in range(1, 5))):
                assert bound <= s["t1"] <= HORIZON
                assert s["t0"] < s["t1"]
            assert sum(s["commits"] for s in segs) == res[name].commits
            assert preset_timeline(res, name)[0] in ("o2", "mysql")
        rows = summarize(res)
        assert len(rows) == 2 and rows[0].startswith("a,")

    def test_packed_segment_substrate_bitexact_per_lane(self):
        """run_packed_segment (the substrate shared by the governed runner
        and the sweep compaction scheduler) must equal per-lane
        _run_seg_dyn in EVERY state leaf — heterogeneous protocols,
        drift-schedule workloads, per-lane untils, and the device-
        resident packed resume (``packed=``) included."""
        from repro.sweep.runner import run_packed_segment, _take
        drift = hot_migration(
            WorkloadSpec(kind="hotspot_update", txn_len=2, n_rows=1024),
            4, n_sites=4, period=1)
        cfg0 = EngineConfig(protocol=protocol_params("group"),
                            costs=CostModel(), workload=drift.spec(0),
                            n_threads=8, horizon=HORIZON)
        stat, _ = split_config(cfg0, pad_threads=64)
        protos = ("group", "mysql", "o2")
        dps, states = [], []
        for i, proto in enumerate(protos):
            _, dp = split_config(dataclasses.replace(
                cfg0, protocol=protocol_params(proto),
                workload=drift.spec(i)), pad_threads=64)
            dps.append(dp)
            states.append(E.init_state_dyn(stat, dp))
        untils = [10_000, 14_000, 18_000]
        packed, snaps, w = run_packed_segment(stat, dps, states, untils)
        assert w == 4                       # 3 lanes pow2-padded
        # second segment resumes from the packed stack, no re-pack
        untils2 = [20_000, 24_000, 28_000]
        packed2, snaps2, _ = run_packed_segment(stat, dps, None, untils2,
                                                packed=packed)
        for i in range(3):
            ref, ref_snap = E.run_segment(stat, dps[i], states[i],
                                          untils[i])
            for a, b in zip(jax.tree.leaves(_take(packed, i)),
                            jax.tree.leaves(ref)):
                assert (np.asarray(a) == np.asarray(b)).all()
            for a, b in zip(jax.tree.leaves(_take(snaps, i)),
                            jax.tree.leaves(ref_snap)):
                assert (np.asarray(a) == np.asarray(b)).all()
            ref2, _ = E.run_segment(stat, dps[i], ref, untils2[i])
            for a, b in zip(jax.tree.leaves(_take(packed2, i)),
                            jax.tree.leaves(ref2)):
                assert (np.asarray(a) == np.asarray(b)).all()

    def test_batched_lanes_match_sequential(self):
        """chunk_size>1 (vmapped segmented lanes) must be bit-identical
        to the sequential per-lane path, switches included."""
        drift = skew_ramp(ZIPF, 3, lo=0.3, hi=1.1)

        def cells():
            return [GovernorCell("r", QueueRulePolicy(), drift, 8),
                    GovernorCell("m", FixedPolicy("mysql"), drift, 12),
                    GovernorCell("g", FixedPolicy("group"), drift, 8)]

        seq = run_governed(cells(), horizon=HORIZON, n_segments=3,
                           chunk_size=1)
        bat = run_governed(cells(), horizon=HORIZON, n_segments=3,
                           chunk_size=4)
        for name in ("r", "m", "g"):
            for f in METRIC_FIELDS:
                assert getattr(seq[name], f) == getattr(bat[name], f), \
                    (name, f)
            assert seq.segments[name] == bat.segments[name]

    def test_duplicate_cell_names_rejected(self):
        drift = stationary(ZIPF, 2)
        cells = [GovernorCell("x", FixedPolicy("o2"), drift, 8)] * 2
        with pytest.raises(ValueError, match="duplicate"):
            run_governed(cells, horizon=1000, n_segments=2)


class TestStoreV3:
    def test_roundtrip_with_segments(self, tmp_path):
        drift = stationary(ZIPF, 3)
        res = run_governed(
            [GovernorCell("cell", FixedPolicy("o2"), drift, 8)],
            horizon=HORIZON, n_segments=3)
        path = os.path.join(tmp_path, "gov.json")
        save_results(path, res, meta={"tag": "t"})
        doc = load_results(path)
        assert doc["schema"] == "repro.sweep/v4"
        rec = doc["points"][0]
        assert len(rec["segments"]) == 3
        assert rec["segments"][0]["preset"] == "o2"
        assert rec["metrics"]["commits"] == res["cell"].commits
        # v3 additions: per-window breakdown conserves to pad_T * window,
        # distribution histograms count every row / every hot row
        pad_t = 64      # MIN_T_BUCKET pads the 8 threads up
        for seg in rec["segments"]:
            bd = seg["breakdown"]
            assert set(bd) == set(E.TB_NAMES)
            assert sum(bd.values()) == pad_t * (seg["t1"] - seg["t0"])
            assert sum(seg["wait_hist"]) == ZIPF.n_rows
            assert sum(seg["occ_hist"]) == seg["n_hot"]

    def test_v1_v2_documents_still_load(self, tmp_path):
        for old in ("repro.sweep/v1", "repro.sweep/v2"):
            path = os.path.join(tmp_path, old.replace("/", "_") + ".json")
            with open(path, "w") as f:
                json.dump({"schema": old, "points": []}, f)
            assert load_results(path)["schema"] == old

    def test_foreign_json_rejected(self, tmp_path):
        path = os.path.join(tmp_path, "x.json")
        with open(path, "w") as f:
            json.dump({"schema": "something/else"}, f)
        with pytest.raises(ValueError):
            load_results(path)


class TestBrookSwitchIn:
    """Switching INTO brook2pl mid-run (governor.py preset-table note):
    in-flight transactions generated under the previous preset can hold
    locks out of chop order and form a cycle pure brook2pl can never
    resolve (no detection, no timeouts). run_governed rejects such
    switches loudly (switch_safe); `brook_guard` re-arms the wait
    timeout as the residual resolver and must recover."""

    class _Switch(Policy):
        def __init__(self, first, then):
            self.first, self.then = first, then
            self.name = f"switch:{first}->{then}"

        def decide(self, k, history):
            return self.first if k == 0 else self.then

    W = WorkloadSpec(kind="zipf", zipf_s=1.1, txn_len=4, n_rows=256)

    def _run(self, then, n_seg=6):
        cell = GovernorCell(f"swt_{then}", self._Switch("mysql", then),
                            stationary(self.W, n_seg), 64)
        return run_governed([cell], horizon=240_000, n_segments=n_seg)

    def test_pure_brook_switch_in_rejected_loudly(self):
        """An unresolvable inherited stall must not be a silent flatline:
        the runner refuses the switch and names the safe preset."""
        from repro.adaptive import switch_safe
        assert not switch_safe("brook2pl")
        assert not switch_safe("brook_hold")
        assert switch_safe("brook_guard") and switch_safe("mysql")
        with pytest.raises(ValueError, match="brook_guard"):
            self._run("brook2pl")

    def test_brook_to_brook_switches_allowed(self):
        """Chop-ordered in-flight txns make resolver-free targets safe:
        brook_guard -> brook2pl must NOT be rejected (same acquisition
        order, nothing to inherit a cycle from)."""
        cell = GovernorCell("swt_gp", self._Switch("brook_guard",
                                                   "brook2pl"),
                            stationary(self.W, 4), 64)
        res = run_governed([cell], horizon=120_000, n_segments=4)
        assert res["swt_gp"].forced_aborts == 0
        assert res["swt_gp"].commits > 0

    def test_brook_guard_switch_in_recovers(self):
        """The guarded variant times the inherited cycle out and then
        runs deadlock-free brook traffic for the rest of the horizon."""
        res = self._run("brook_guard")
        commits = [s["commits"] for s in res.segments["swt_brook_guard"]]
        assert sum(commits[2:]) > 0, commits
        assert commits[-1] > 0, commits

    def test_two_hop_guard_bypass_rejected(self):
        """mysql -> brook_guard -> brook2pl: a one-segment guard hop
        does not launder unordered-era locks (its timeout may not have
        fired within the segment) — resolver-free presets require an
        ordered history all the way back to segment 0."""
        class _TwoHop(Policy):
            name = "twohop"

            def decide(self, k, history):
                return ("mysql", "brook_guard", "brook2pl")[min(k, 2)]

        cell = GovernorCell("swt_2hop", _TwoHop(), stationary(self.W, 4),
                            64)
        with pytest.raises(ValueError, match="unordered-preset"):
            run_governed([cell], horizon=120_000, n_segments=4)

    def test_rank_rotating_drift_rejected_for_pure_brook(self):
        """hot_migration rotates acq_rank between segments: in-flight
        and new transactions would disagree about the lock order with no
        resolver (measured: permanent flatline) — must raise instead,
        while brook_guard rides the same drift fine."""
        drift = hot_migration(self.W, 6, n_sites=2, period=1)
        cell = GovernorCell("mig_brook", FixedPolicy("brook2pl"), drift,
                            64)
        with pytest.raises(ValueError, match="rank"):
            run_governed([cell], horizon=120_000, n_segments=6)
        cell2 = GovernorCell("mig_guard", FixedPolicy("brook_guard"),
                             drift, 64)
        res = run_governed([cell2], horizon=120_000, n_segments=6)
        assert res["mig_guard"].commits > 0

    def test_stable_rank_drift_allowed_for_pure_brook(self):
        """skew_ramp changes zipf_s but never the key-heat ORDER, so the
        rank table is stable and fixed brook2pl stays legal and clean."""
        w = dataclasses.replace(self.W, zipf_s=0.7)
        drift = skew_ramp(w, 4, lo=0.3, hi=0.9)
        cell = GovernorCell("ramp_brook", FixedPolicy("brook2pl"), drift,
                            64)
        res = run_governed([cell], horizon=120_000, n_segments=4)
        assert res["ramp_brook"].forced_aborts == 0
        assert res["ramp_brook"].dd_ticks == 0
        assert res["ramp_brook"].commits > 0

    def test_fixed_brook_guard_no_false_timeouts(self):
        """The guard timeout must never fire on brook-generated waits:
        a brook_guard run from segment 0 pays zero forced aborts (the
        property the preset comment claims). At 240k/6 the derived
        guard sits on GUARD_FLOOR — this is also the floor's no-false-
        timeout certification."""
        from repro.adaptive import GUARD_FLOOR, guard_timeout
        assert guard_timeout(240_000, 6) == GUARD_FLOOR
        cell = GovernorCell("fx_guard", FixedPolicy("brook_guard"),
                            stationary(self.W, 6), 64)
        res = run_governed([cell], horizon=240_000, n_segments=6)
        assert res["fx_guard"].forced_aborts == 0
        assert res["fx_guard"].dd_ticks == 0
        assert res["fx_guard"].commits > 0

    def test_guard_timeout_derivation(self):
        """guard_timeout = half a segment clamped to [floor, cap]; the
        derivation only rewrites presets that re-arm the timeout as a
        resolver (brook_guard), never protocol-semantic timeouts."""
        from repro.adaptive import (GUARD_CAP, GUARD_FLOOR, guard_timeout,
                                    preset_params)
        assert guard_timeout(480_000, 4) == 60_000
        assert guard_timeout(240_000, 6) == GUARD_FLOOR      # clamp up
        assert guard_timeout(2_000_000, 4) == GUARD_CAP      # clamp down
        g = preset_params("brook_guard", horizon=480_000, n_segments=4)
        assert g.wait_timeout == 60_000
        assert g.commit_wait_timeout == 60_000
        # context-free callers keep the fixed fallback
        assert preset_params("brook_guard").wait_timeout == 100_000
        # semantic timeouts untouched: mysql's default, brook2pl's 0
        assert preset_params("mysql", horizon=480_000,
                             n_segments=4).wait_timeout == \
            preset_params("mysql").wait_timeout
        assert preset_params("brook2pl", horizon=480_000,
                             n_segments=4).wait_timeout == 0
        # derived guard still counts as switch-safe (resolver present)
        from repro.adaptive import switch_safe
        assert switch_safe("brook_guard")

    def test_brook_guard_last_boundary_switch_recovers(self):
        """The ROADMAP case the fixed 100k guard could not serve: a
        switch-in at the LAST segment boundary of a coarse-segment run.
        Segments of 120k ticks derive a 60k guard — the inherited stall
        times out with half the final segment left, so the tail segment
        still commits. (The fixed guard would fire 100k in, leaving
        only noise-level room before the horizon.)"""
        from repro.adaptive import guard_timeout
        n_seg, horizon = 4, 480_000
        assert guard_timeout(horizon, n_seg) == 60_000

        class _LastHop(Policy):
            name = "lasthop"

            def decide(self, k, history):
                return "brook_guard" if k == n_seg - 1 else "mysql"

        cell = GovernorCell("swt_late", _LastHop(),
                            stationary(self.W, n_seg), 64)
        res = run_governed([cell], horizon=horizon, n_segments=n_seg)
        segs = res.segments["swt_late"]
        assert segs[-1]["preset"] == "brook_guard"
        assert segs[-1]["commits"] > 0, [s["commits"] for s in segs]
