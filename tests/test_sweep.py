"""Sweep-subsystem tests: bit-exact parity between vmapped sweep lanes and
per-config ``simulate()`` runs (the subsystem's core contract) on both the
sort-then-cut and lockstep-compaction execution paths, padding / masking
invariance for heterogeneous grids, lane sharding, compile accounting,
grid builders, and the JSON results store."""
import dataclasses
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from lock_matrix import SHAPES, each_kind
from repro.core.lock import (PROTOCOLS, CostModel, WorkloadSpec, extract,
                             extract_aria, simulate, simulate_aria)
from repro.sweep import (expand, grid, load_results, point, run_sweep,
                         save_results, summarize, zip_grid)

HOT = WorkloadSpec(kind="hotspot_update", txn_len=1, n_rows=512)
ZIPF = WorkloadSpec(kind="zipf", txn_len=2, n_rows=256, zipf_s=0.9)
HORIZON = 25_000

INT_FIELDS = ("commits", "user_aborts", "forced_aborts", "lock_ops",
              "iters", "dd_ticks")
FLOAT_FIELDS = ("tps", "mean_latency_us", "p95_latency_us", "abort_rate",
                "lock_wait_frac", "cpu_util")


def reference(p):
    """Per-config result via the plain simulate() path."""
    if p.protocol == "aria":
        s = simulate_aria(p.workload, p.n_threads, costs=p.costs,
                          horizon=p.horizon)
        return extract_aria(p.n_threads, s)
    s = simulate(p.protocol, p.workload, p.n_threads, costs=p.costs,
                 horizon=p.horizon, p_abort=p.p_abort, drain=p.drain,
                 **p.over())
    return extract(p.protocol, p.n_threads, s)


def assert_bitexact(r_sweep, r_ref, name):
    for f in INT_FIELDS:
        assert getattr(r_sweep, f) == getattr(r_ref, f), (name, f)
    for f in FLOAT_FIELDS:
        assert getattr(r_sweep, f) == getattr(r_ref, f), (name, f)


class TestParity:
    def test_vmapped_grid_matches_simulate_bitexact(self):
        """Heterogeneous protocols/threads/p_abort, forced vmap chunks:
        every lane must equal its per-config run bit-for-bit (threads are
        padded to the 64-floor bucket, so padding is exercised too)."""
        pts = grid(["mysql", "group", "bamboo"], HOT, [8, 12],
                   horizon=HORIZON, p_abort=[0.0, 0.1],
                   name_fmt="{protocol}_T{n_threads}_p{p_abort}")
        res = run_sweep(pts, chunk_size=4)
        for p in pts:
            assert_bitexact(res[p.name], reference(p), p.name)

    def test_heterogeneous_txn_len_padding(self):
        """Mixed txn lengths land in distinct buckets; zipf keys flow
        through the traced CDF identically on both paths."""
        pts = [point("group", ZIPF, 8, horizon=HORIZON, name="zl2"),
               point("group", dataclasses.replace(ZIPF, txn_len=4), 8,
                     horizon=HORIZON, name="zl4")]
        res = run_sweep(pts, chunk_size=2)
        for p in pts:
            assert_bitexact(res[p.name], reference(p), p.name)

    def test_max_bucket_pads_txn_len(self):
        """thread_bucket="max" runs the short-txn lane with padded op
        slots (L=2 lane in an L=4 program) — padding must stay bitwise
        invisible (nops stops the op cursor before padded slots)."""
        pts = [point("mysql", ZIPF, 8, horizon=HORIZON, name="mx2"),
               point("mysql", dataclasses.replace(ZIPF, txn_len=4), 12,
                     horizon=HORIZON, name="mx4")]
        res = run_sweep(pts, chunk_size=2, thread_bucket="max")
        assert len(res.buckets) == 1
        assert res.buckets[0].pad_len == 4
        for p in pts:
            assert_bitexact(res[p.name], reference(p), p.name)

    @each_kind
    def test_protocol_lanes_match_simulate_bitexact(self, kind):
        """Lanes of all six tick-engine protocols in ONE vmapped program
        (sort-then-cut, one executable per kind) equal their per-config
        ``simulate()`` runs bit-for-bit: the protocol branches lower to
        selects under vmap, so every lane runs every family's code."""
        wl, n_threads = SHAPES[kind]
        pts = grid(list(PROTOCOLS), wl, [n_threads], horizon=HORIZON,
                   p_abort=0.1, name_fmt="{protocol}")
        res = run_sweep(pts, chunk_size=len(pts), compact=False)
        assert [b.n_chunks for b in res.buckets] == [1]
        for p in pts:
            assert_bitexact(res[p.name], reference(p), p.name)

    def test_aria_lanes_match(self):
        pts = grid("aria", HOT, [8, 16], horizon=HORIZON)
        res = run_sweep(pts, chunk_size=2)
        for p in pts:
            assert_bitexact(res[p.name], reference(p), p.name)

    def test_proto_override_flows_through(self):
        pts = [point("group", HOT, 16, horizon=HORIZON, name="gc_off",
                     group_commit=False)]
        res = run_sweep(pts)
        assert_bitexact(res["gc_off"], reference(pts[0]), "gc_off")

    def test_aria_rejects_unsupported_params(self):
        """Aria has no abort injection/drain; a sweep must refuse rather
        than silently run defaults under a name that claims them."""
        pts = [point("aria", HOT, 8, horizon=HORIZON, p_abort=0.1,
                     name="aria_p0.1")]
        with pytest.raises(ValueError, match="aria does not support"):
            run_sweep(pts)

    def test_unknown_protocol_fails_loudly(self):
        """A typo'd protocol must raise up front, not degrade silently
        (the old _est_iters bare-except hid it behind a worse chunking
        order until a cryptic KeyError deep in the bucket loop)."""
        pts = [point("br00k2pl", HOT, 8, horizon=1000, name="b2pl")]
        with pytest.raises(ValueError, match="unknown protocol"):
            run_sweep(pts)

    def test_brook2pl_lanes_match_simulate_bitexact(self):
        """brook2pl is a first-class sweep protocol now (PR 4 made it a
        ValueError): vmapped lanes — chop-ordered acquisition, per-op
        release, injected aborts — must equal per-config ``simulate()``
        bit-for-bit in one compile per shape bucket, with zero deadlock
        rollbacks and zero detection ticks."""
        w = dataclasses.replace(ZIPF, n_rows=251)   # unique shape: cold
        pts = grid(["brook2pl", "mysql"], w, [8, 12], horizon=HORIZON,
                   p_abort=[0.0, 0.1],
                   name_fmt="{protocol}_T{n_threads}_p{p_abort}")
        res = run_sweep(pts, chunk_size=4)
        assert len(res.buckets) == 1
        assert res.n_compiles <= 4          # the pow2 width ladder, once
        for p in pts:
            r = res[p.name]
            assert_bitexact(r, reference(p), p.name)
            if p.protocol == "brook2pl":
                assert r.forced_aborts == 0 and r.dd_ticks == 0, p.name

    def test_est_iters_covers_brook2pl_without_warning(self):
        """The analytic model covers the new protocol, so the warn-once
        fallback must NOT fire on brook2pl sweeps (satellite: the warn
        path is for protocols that land BEFORE their ref model)."""
        from repro.sweep import runner as R
        R._EST_WARNED.clear()
        pts = grid(["brook2pl"], HOT, [8, 64], horizon=HORIZON)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ests = [R._est_iters(p) for p in pts]
        assert all(e > 0 for e in ests)
        assert ests[0] > 0 and not w, [str(x.message) for x in w]
        # denser-thread config never estimates below the single lane
        assert ests[1] >= ests[0] * 0.99

    def test_est_iters_ref_model_gap_warns_once_and_falls_back(self,
                                                               monkeypatch):
        """A protocol the analytic model doesn't cover degrades the
        scheduling estimate with ONE warning — while real bugs (any other
        exception type) propagate."""
        from repro.sweep import runner as R
        import repro.core.lock.ref_engine as ref

        def boom(*a, **k):
            raise ValueError("no chain model for this knob combo")

        monkeypatch.setattr(ref, "predicted_tps", boom)
        R._EST_WARNED.clear()
        pts = grid(["mysql", "o2"], HOT, [8, 12], horizon=HORIZON)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ests = [R._est_iters(p) for p in pts]
        assert all(e > 0 for e in ests)
        assert len([x for x in w if x.category is RuntimeWarning]) == 2
        # one warning per protocol, not per point

        def bug(*a, **k):
            raise TypeError("a real bug")

        monkeypatch.setattr(ref, "predicted_tps", bug)
        R._EST_WARNED.clear()
        with pytest.raises(TypeError, match="a real bug"):
            R._est_iters(pts[0])


class TestCompaction:
    """The lockstep-compaction scheduler (default whenever chunk_size > 1)
    must be bit-identical to per-config ``simulate()`` — including the
    ``iters`` diagnostic, since pausing a lane at an iteration budget and
    resuming replays the identical step sequence — while paying fewer
    vmapped lane-iterations on mixed-density grids."""

    def test_partial_pack_replicated_pad(self):
        """5 lanes in an 8-wide request: the pack pads to pow2 by
        replicating the last lane; padded copies must stay invisible."""
        pts = grid(["mysql", "group", "o2", "bamboo", "o1"], HOT, 8,
                   horizon=HORIZON)
        res = run_sweep(pts, chunk_size=8, compact=True)
        assert all(b.compacted for b in res.buckets)
        for p in pts:
            assert_bitexact(res[p.name], reference(p), p.name)

    def test_thread_and_txn_len_padded_lanes(self):
        """Compacted lanes at padded shapes (T to the pow2-64 floor, L to
        the max-bucket cap) keep padding bitwise invisible."""
        pts = [point("mysql", ZIPF, 8, horizon=HORIZON, name="mz2"),
               point("group", dataclasses.replace(ZIPF, txn_len=4), 12,
                     horizon=HORIZON, name="gz4"),
               point("o2", dataclasses.replace(ZIPF, txn_len=4), 24,
                     horizon=HORIZON, name="oz4")]
        res = run_sweep(pts, chunk_size=4, compact=True,
                        thread_bucket="max")
        assert len(res.buckets) == 1
        assert res.buckets[0].pad_len == 4
        for p in pts:
            assert_bitexact(res[p.name], reference(p), p.name)

    def test_drain_lanes_retire_on_quiescence(self):
        """drain=True lanes end when every thread HALTs (not at the
        horizon), so the host-side retire check must track the device
        cond's live-threads clause."""
        pts = grid(["mysql", "group"], HOT, [4, 8], horizon=12_000,
                   drain=True, name_fmt="d_{protocol}_T{n_threads}")
        res = run_sweep(pts, chunk_size=4, compact=True)
        for p in pts:
            assert_bitexact(res[p.name], reference(p), p.name)

    def test_aria_barrier_path_staggered_costs(self):
        """Aria lanes with different batch times (sync_lat axis) retire at
        staggered calls; segmented batch execution must replay the exact
        batch sequence."""
        pts = zip_grid("aria", HOT, [8, 8, 16], horizon=HORIZON,
                       costs=[CostModel(), CostModel(sync_lat=3_000),
                              CostModel(sync_lat=9_000)],
                       name_fmt="aria_T{n_threads}_s{sync_lat}")
        # 16-batch slices: the sync_lat=9000 lane (~3 batches total)
        # retires on call 1 while the sync_lat=0 lane (~80) keeps going
        res = run_sweep(pts, chunk_size=4, compact=True, slice_iters=16)
        for p in pts:
            assert_bitexact(res[p.name], reference(p), p.name)
        assert res.n_repacks >= 1       # short lanes left the pack early

    def test_mixed_density_cuts_lane_iters_2x(self):
        """The acceptance scenario: detection-free protocols deadlock-stall
        on multi-row zipf at T>=16 (tens of iterations) while detection
        protocols churn (thousands) — a mix the iteration ESTIMATE cannot
        see, so sort-then-cut locksteps them. Compaction must cut total
        vmapped lane-iterations >= 2x and repack at least once, while
        staying bit-identical."""
        w = dataclasses.replace(ZIPF, n_rows=512)
        mk = lambda pr, t: point(pr, w, t, horizon=60_000,
                                 name=f"{pr}_T{t}")
        pts = [mk("o1", 16), mk("mysql", 16),
               mk("o2", 16), mk("o2", 32), mk("o2", 64),
               mk("group", 16), mk("group", 32), mk("group", 64)]
        res_n = run_sweep(pts, chunk_size=8, compact=False)
        res_c = run_sweep(pts, chunk_size=8, compact=True)
        for p in pts:
            ref = reference(p)
            assert_bitexact(res_c[p.name], ref, p.name)
            assert_bitexact(res_n[p.name], ref, p.name)
        assert res_c.n_repacks >= 1
        assert res_n.lane_iters >= 2 * res_c.lane_iters, \
            (res_n.lane_iters, res_c.lane_iters)
        # the store carries the per-call repack log
        log = res_c.buckets[0].repack_log
        assert log and all(len(rec) == 3 for rec in log)

    def test_adaptive_budget_recovers_from_bad_estimate(self, monkeypatch):
        """PR4 follow-on (b): with `slice_iters` unset the budget
        re-derives from the observed per-call progress, so an analytic
        estimate that's 1000x off costs a handful of re-calibrated calls
        — not total-iters/256 fixed slices (what `slice_iters=256` pins,
        standing in for the old static behavior). Parity must hold on
        every path and mixed-density repack counts must not regress."""
        from repro.sweep import runner as R
        w = dataclasses.replace(ZIPF, n_rows=512)
        mk = lambda pr, t: point(pr, w, t, horizon=120_000,
                                 name=f"{pr}_T{t}")
        pts = [mk("o1", 16), mk("mysql", 16), mk("o2", 16),
               mk("group", 16)]
        monkeypatch.setattr(R, "_est_iters", lambda p: 1.0)
        res_static = run_sweep(pts, chunk_size=4, compact=True,
                               slice_iters=256)
        res_adapt = run_sweep(pts, chunk_size=4, compact=True)
        for p in pts:
            ref = reference(p)
            assert_bitexact(res_adapt[p.name], ref, p.name)
            assert_bitexact(res_static[p.name], ref, p.name)
        calls_a = sum(b.n_chunks for b in res_adapt.buckets)
        calls_s = sum(b.n_chunks for b in res_static.buckets)
        assert calls_a < calls_s, (calls_a, calls_s)
        # compaction still engages: the stalled detection-free lanes
        # retire early and the pack repacks down, adaptive or not
        assert res_adapt.n_repacks >= 1
        # re-deriving the budget must not blow up the lockstep cost
        assert res_adapt.lane_iters <= int(1.25 * res_static.lane_iters)


SUB_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_sub(code: str, timeout=300):
    env = dict(os.environ)
    env["PYTHONPATH"] = SUB_SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-3000:])
    return out.stdout


class TestLaneSharding:
    def test_nondividing_lane_count_pads_and_engages(self):
        """Regression: _shard_lanes used to silently skip sharding when
        n_lanes % n_dev != 0 (e.g. 12 lanes on 8 devices ran on one
        device). It must now pad the lane axis to a device multiple
        (replicated tail) and place lanes across the whole mesh — and
        sweep results must stay bit-identical. 3 forced host devices so
        pow2 pack widths never divide evenly."""
        code = (
            "import os;"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=3';"
            "import jax, numpy as np, jax.numpy as jnp;"
            "from repro.core.lock import WorkloadSpec, simulate, extract;"
            "from repro.sweep import grid, run_sweep;"
            "from repro.sweep import runner as R;"
            "assert len(jax.devices()) == 3;"
            "tree = {'x': jnp.arange(8.).reshape(4, 2)};"
            "sh, g = R._shard_lanes(tree, 4);"
            "assert g == 6, g;"
            "assert sh['x'].shape == (6, 2), sh['x'].shape;"
            "x = np.asarray(sh['x']);"
            "assert (x[4] == x[3]).all() and (x[5] == x[3]).all();"
            "assert len(sh['x'].sharding.device_set) == 3;"
            "HOT = WorkloadSpec(kind='hotspot_update', txn_len=1,"
            " n_rows=512);"
            "pts = grid(['mysql', 'o2', 'group'], HOT, [8, 12],"
            " horizon=20_000, name_fmt='{protocol}_T{n_threads}');"
            "res_c = run_sweep(pts, chunk_size=4);"
            "res_n = run_sweep(pts, chunk_size=4, compact=False);\n"
            "for p in pts:\n"
            "  r = extract(p.protocol, p.n_threads, simulate(p.protocol,"
            " p.workload, p.n_threads, horizon=p.horizon))\n"
            "  for res in (res_c, res_n):\n"
            "    got = res[p.name]\n"
            "    assert (got.commits, got.iters, got.tps) =="
            " (r.commits, r.iters, r.tps), p.name\n"
            "print('sharded-parity-ok', res_c.n_repacks)\n"
        )
        out = _run_sub(code)
        assert "sharded-parity-ok" in out


class TestCompileAccounting:
    def test_64_grid_one_compile_per_bucket(self):
        """A 64-config (protocol x threads x p_abort x costs) grid over one
        shape bucket: chunked vmap execution, exactly one engine compile
        (unique n_rows guarantees a cold cache for this shape)."""
        w = dataclasses.replace(HOT, n_rows=509)
        pts = grid(["mysql", "o1", "o2", "group"], w, [4, 8, 16, 32],
                   horizon=15_000, p_abort=[0.0, 0.05],
                   costs=[CostModel(), CostModel(sync_lat=1_000)],
                   name_fmt="{protocol}_T{n_threads}_p{p_abort}_s{sync_lat}")
        assert len(pts) == 64
        res = run_sweep(pts, chunk_size=16, compact=False)
        assert len(res.buckets) == 1        # one shape bucket (T floor 64)
        assert res.buckets[0].n_chunks == 4
        assert res.n_compiles == 1
        # sampled per-config parity on the same grid
        rng = np.random.default_rng(0)
        for i in rng.choice(len(pts), size=4, replace=False):
            assert_bitexact(res[pts[i].name], reference(pts[i]),
                            pts[i].name)

    def test_compacted_width_ladder_bounds_executables(self):
        """Compaction trades the chunked path's single executable for a
        bounded pow2 width ladder: full packs at chunk_size, the drain
        tail at shrinking pow2 widths — never more than
        log2(chunk) + 2 programs per cold shape."""
        w = dataclasses.replace(HOT, n_rows=503)    # unique shape: cold
        pts = grid(["mysql", "o1", "o2", "group"], w, [4, 8, 16],
                   horizon=15_000, name_fmt="{protocol}_T{n_threads}")
        res = run_sweep(pts, chunk_size=8, compact=True, slice_iters=64)
        assert res.n_compiles <= 5          # widths {8,4,2} + _run_dyn + 1
        # the same sweep again reuses every ladder executable
        res2 = run_sweep(pts, chunk_size=8, compact=True, slice_iters=64)
        assert res2.n_compiles == 0

    def test_chunk_reuse_second_sweep_compiles_nothing(self):
        w = dataclasses.replace(HOT, n_rows=509)
        pts = grid(["mysql", "o2"], w, [4, 8], horizon=15_000)
        run_sweep(pts, chunk_size=4)
        res2 = run_sweep(pts, chunk_size=4)
        assert res2.n_compiles == 0


class TestGridBuilders:
    def test_cartesian_counts_and_names(self):
        pts = grid(["mysql", "o2"], {"hot": HOT}, [8, 16], horizon=1000,
                   p_abort=[0.0, 0.1],
                   name_fmt="{protocol}_{workload}_T{n_threads}_p{p_abort}")
        assert len(pts) == 8
        assert len({p.name for p in pts}) == 8
        assert pts[0].name.startswith(("mysql_hot", "o2_hot"))

    def test_zip_grid_pairs_and_broadcasts(self):
        pts = zip_grid(["mysql", "o2", "group"], HOT, 8, horizon=1000,
                       costs=[CostModel(sync_lat=s) for s in (0, 10, 20)])
        assert len(pts) == 3
        assert [p.costs.sync_lat for p in pts] == [0, 10, 20]
        with pytest.raises(ValueError):
            zip_grid(["mysql", "o2"], HOT, [1, 2, 3], horizon=1000)

    def test_expand_workload_fields(self):
        ws = expand(ZIPF, tag_fmt="sf{zipf_s}", zipf_s=[0.7, 0.99])
        assert [t for t, _ in ws] == ["sf0.7", "sf0.99"]
        assert ws[1][1].zipf_s == 0.99

    def test_duplicate_names_rejected(self):
        pts = grid("mysql", HOT, 8, horizon=1000) * 2
        with pytest.raises(ValueError, match="duplicate"):
            run_sweep(pts)


class TestStore:
    def test_roundtrip(self, tmp_path):
        pts = grid(["mysql", "o2"], HOT, 8, horizon=HORIZON)
        res = run_sweep(pts)
        path = os.path.join(tmp_path, "sweep.json")
        save_results(path, res, meta={"tag": "t"})
        doc = load_results(path)
        assert doc["meta"]["tag"] == "t"
        assert doc["n_points"] == 2
        names = [r["name"] for r in doc["points"]]
        assert names == [p.name for p in pts]
        rec = doc["points"][0]
        assert rec["metrics"]["commits"] == res[rec["name"]].commits
        assert rec["workload"]["kind"] == "hotspot_update"
        # summarize emits one benchmark CSV row per point, in order
        rows = summarize(res)
        assert len(rows) == 2 and rows[0].startswith(pts[0].name + ",")

    def test_load_rejects_foreign_json(self, tmp_path):
        path = os.path.join(tmp_path, "x.json")
        with open(path, "w") as f:
            json.dump({"hello": 1}, f)
        with pytest.raises(ValueError):
            load_results(path)
