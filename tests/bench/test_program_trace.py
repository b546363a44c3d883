"""The program's own labels in a trace: the idle split among host spans,
the per-operation device times, the readers of the per-layer metrics
built on them, the serve loop's spans, and the stage map of the engine
entry a cell's driver runs."""
import glob
import os

import pytest

from bench import program_trace, tracing
from bench.layers import (engine_us_per_iter, seg_snapshot_ms_per_boundary,
                          serve_idle_unattributed_ms_per_boundary,
                          serve_lanes_ms_per_boundary,
                          serve_rebuild_ms_per_boundary,
                          serve_sync_ms_per_boundary,
                          stage_derive_us_per_iter, stage_gen_txn_us_per_iter,
                          stage_grant_us_per_iter, stage_group_us_per_iter,
                          stage_tick_charge_us_per_iter,
                          stage_unscoped_us_per_iter)

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")
STAGE_READERS = (stage_gen_txn_us_per_iter, stage_derive_us_per_iter,
                 stage_grant_us_per_iter, stage_group_us_per_iter,
                 stage_tick_charge_us_per_iter, stage_unscoped_us_per_iter)
SERVE_SPANS = ("repro.serve.rebuild", "repro.serve.segment",
               "repro.serve.device_get", "repro.serve.lanes")


def test_idle_split_partitions_idle_time_exactly():
    spans = [(0, 100, "a"), (10, 20, "b"), (50, 60, "c"), (150, 200, "d")]
    gaps = [(5, 15), (18, 55), (95, 160), (300, 310)]
    got = program_trace.split_idle(gaps, spans)
    assert got == {"a": 5 + 30 + 5, "b": 5 + 2, "c": 5, "d": 10,
                   "none": 50 + 10}
    assert sum(got.values()) == sum(b - a for a, b in gaps)
    # no spans: all of it is 'none'; no gaps: nothing at all
    assert program_trace.split_idle(gaps, []) == {"none": 122}
    assert program_trace.split_idle([], spans) == {}


def test_innermost_span_is_the_latest_started():
    pieces = program_trace._innermost([(0, 10, "outer"), (2, 4, "inner")])
    assert pieces == [[0, 2, "outer"], [2, 4, "inner"], [4, 10, "outer"]]


@pytest.fixture(scope="module")
def recorded():
    return tracing.reduce(TINY), program_trace.read_file(TINY)


def test_recorded_trace_agrees_with_reduce(recorded):
    summary, pt = recorded
    # the recording predates the program's spans: all idle is 'none'
    assert set(pt.idle_split) == {"none"} and not pt.span_self
    assert pt.idle_split["none"] == pytest.approx(summary.idle_s,
                                                  rel=1e-12)
    # per-(module, op) self times add up to reduce's per-op ones
    by_op = {}
    for (_, op), t in pt.op_self.items():
        by_op[op] = by_op.get(op, 0.0) + t
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:tracing.TOP_N]
    assert [n for n, _ in top] == [n for n, _ in summary.device_ops]
    assert [t for _, t in top] == pytest.approx(
        [t for _, t in summary.device_ops], rel=1e-9)
    engine = {m for m, _ in pt.op_self if "_run_dyn" in m}
    assert engine and engine <= set(summary.modules)


def _obs(modules_s=1.0, iters=1000, boundaries=0, op_self=None,
         stages=None, spans=None, idle=None):
    summary = tracing.TraceSummary(
        window_s=2.0, busy_s={0: 1.5}, device_ops=[], idle_gaps=[],
        modules={"jit__run_dyn(1)": modules_s, "jit_other(2)": 0.1})
    pt = program_trace.ProgramTrace(
        op_self=op_self or {}, span_self=spans or {},
        span_count={n: 1 for n in spans or {}},
        idle_split=idle or {"none": 0.5})
    return {"trace": summary,
            "counters": {"traced_iters": iters,
                         "traced_boundaries": boundaries},
            "program_trace": pt, "op_stages": stages}


def _engine_ops():
    op_self = {("jit__run_dyn(1)", "fusion.1"): 0.30,
               ("jit__run_dyn(1)", "fusion.2"): 0.20,
               ("jit__run_dyn(1)", "scatter.3"): 0.10,
               ("jit__run_dyn(1)", "sort.4"): 0.05,
               ("jit__run_dyn(1)", "conditional.5"): 0.05,
               ("jit__run_dyn(1)", "copy.6"): 0.10,
               ("jit_other(2)", "fusion.1"): 0.1}
    stages = {"fusion.1": "stage_derive", "fusion.2": "stage_tick_charge",
              "scatter.3": "stage_group_lock", "sort.4":
              "stage_ticket_assign", "conditional.5": "stage_deadlock_walk",
              "copy.6": None}
    return op_self, stages


def test_stage_readers_sum_to_engine_us_per_iter():
    op_self, stages = _engine_ops()
    obs = _obs(op_self=op_self, stages=stages)
    got = {r.__name__.split(".")[-1]: r.read(obs) for r in STAGE_READERS}
    assert got["stage_derive_us_per_iter"] == pytest.approx(300.0)
    assert got["stage_tick_charge_us_per_iter"] == pytest.approx(200.0)
    assert got["stage_group_us_per_iter"] == pytest.approx(100.0)
    assert got["stage_gen_txn_us_per_iter"] == pytest.approx(50.0)
    assert got["stage_grant_us_per_iter"] == pytest.approx(50.0)
    # the copy (no scope) and the module's gaps are the remainder
    assert got["stage_unscoped_us_per_iter"] == pytest.approx(300.0)
    assert sum(got.values()) == pytest.approx(engine_us_per_iter.read(obs))


def test_stage_readers_need_a_trace_and_a_map():
    op_self, stages = _engine_ops()
    for obs in (_obs(op_self=op_self, stages=None),
                {**_obs(op_self=op_self, stages=stages), "trace": None,
                 "program_trace": None},
                _obs(op_self=op_self, stages=stages, iters=0)):
        assert all(r.read(obs) is None for r in STAGE_READERS)
        assert seg_snapshot_ms_per_boundary.read(obs) is None


def test_stage_readers_refuse_a_map_of_another_executable():
    op_self, stages = _engine_ops()
    # 0.05 s of 0.80 s of engine op time falls on a name the map lacks
    del stages["sort.4"]
    obs = _obs(op_self=op_self, stages=stages)
    assert all(r.read(obs) is None for r in STAGE_READERS)


def test_seg_snapshot_reader_is_per_boundary():
    op_self = {("jit__run_seg_dyn(3)", "fusion.78"): 0.40,
               ("jit__run_seg_dyn(3)", "fusion.9"): 1.00}
    stages = {"fusion.78": "seg_snapshot", "fusion.9": "stage_derive"}
    obs = _obs(op_self=op_self, stages=stages, boundaries=50)
    assert seg_snapshot_ms_per_boundary.read(obs) == pytest.approx(8.0)


def test_span_readers_are_per_boundary_and_silent_without_spans():
    spans = {"repro.serve.rebuild": 5.0, "repro.serve.device_get": 1.0,
             "repro.serve.lanes": 0.5, "repro.serve.segment": 0.1}
    obs = _obs(boundaries=50, spans=spans,
               idle={"none": 0.25, "repro.serve.rebuild": 4.0})
    assert serve_rebuild_ms_per_boundary.read(obs) == pytest.approx(100.0)
    assert serve_sync_ms_per_boundary.read(obs) == pytest.approx(20.0)
    assert serve_lanes_ms_per_boundary.read(obs) == pytest.approx(10.0)
    assert serve_idle_unattributed_ms_per_boundary.read(obs) == \
        pytest.approx(5.0)
    bare = _obs(boundaries=50, idle={"none": 4.25})
    for r in (serve_rebuild_ms_per_boundary, serve_sync_ms_per_boundary,
              serve_lanes_ms_per_boundary,
              serve_idle_unattributed_ms_per_boundary):
        assert r.read(bare) is None
        assert r.read({**obs, "counters": {}}) is None


def test_serve_writes_one_of_each_span_per_boundary(tiny_cell, tmp_path):
    from jax.profiler import ProfileData
    from bench import calls
    from bench.drivers import serve
    cell = tiny_cell("zipf-mysql-serve")
    tracer = tracing.Tracer(True, str(tmp_path))
    ctx = calls.Context(cell=cell, seed=5, seconds=0.0, tracer=tracer,
                        t_start=0.0)
    n = int(cell.traffic["boundaries"])
    serve._serve(ctx, 1, n, None)                   # compile outside
    tracer.start()
    try:
        with tracer.span("call"):
            serve._serve(ctx, 2, n, serve.BoundaryClock(tracer))
    finally:
        tracer.stop()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans, bench = {}, {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name.startswith("repro.serve."):
                    k = dict(e.stats)["boundary"]
                    spans.setdefault(e.name, []).append((k, iv))
                elif e.name.startswith("bench."):
                    bench.setdefault(e.name, []).append(iv)
    assert sorted(spans) == sorted(SERVE_SPANS)
    (call,), bounds = bench["bench.call"], sorted(bench["bench.boundary"])
    assert len(bounds) == n + 1         # the last one closes the call
    for name in SERVE_SPANS:
        assert sorted(k for k, _ in spans[name]) == list(range(n)), name
        for k, (s, e) in spans[name]:
            assert call[0] <= s and e <= call[1]
            assert bounds[k][0] <= s and e <= bounds[k][1], (name, k)


@pytest.mark.parametrize("name,entry,stage", [
    ("hotspot-group-closed", "_run_dyn", "stage_group_lock"),
    ("zipf-mysql-serve", "_run_seg_dyn", "seg_snapshot")])
def test_stage_map_of_the_drivers_entry(tiny_cell, name, entry, stage):
    from repro.core.lock import engine
    from repro.obs import prof
    cell = tiny_cell(name)
    real = getattr(engine, entry)
    jitted, args = program_trace.entry_args(cell)
    assert jitted is real and getattr(engine, entry) is real
    stat = args[0]
    assert stat.n_rows == cell.config["workload"]["n_rows"]
    stages = prof.stage_map(jitted, *args)
    assert stage in stages.values()
    assert set(stages.values()) - {None} <= set(engine.STAGE_SCOPES)


def test_stage_table_reads_the_call_walls(recorded):
    from bench import stage_table
    walls = stage_table._call_walls(TINY)
    summary, _ = recorded
    assert len(walls) == 2          # two simulate calls were recorded
    assert all(0 < w < summary.window_s for w in walls)
