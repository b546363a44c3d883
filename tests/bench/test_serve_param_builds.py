"""The serve loop's parameter builds: the reader of
``serve_param_builds_per_boundary`` on synthetic traces, and the
``repro.engine.split_config`` span a traced serve call really writes."""
import glob

import pytest

from bench import program_trace, tracing
from bench.layers import serve_param_builds_per_boundary as builds

SPAN = "repro.engine.split_config"


def _obs(span_count, boundaries=50):
    summary = tracing.TraceSummary(
        window_s=2.0, busy_s={0: 1.5}, device_ops=[], idle_gaps=[],
        modules={})
    pt = program_trace.ProgramTrace(
        op_self={}, span_self={n: 0.01 for n in span_count},
        span_count=span_count, idle_split={"none": 0.5})
    return {"trace": summary,
            "counters": {"traced_boundaries": boundaries},
            "program_trace": pt}


@pytest.mark.parametrize("count,boundaries,want", [
    (1, 50, 0.02), (2, 50, 0.04), (3, 3, 1.0)])
def test_builds_per_boundary(count, boundaries, want):
    obs = _obs({SPAN: count, "repro.serve.rebuild": boundaries},
               boundaries)
    assert builds.read(obs) == pytest.approx(want)


def test_silent_without_the_span_or_a_trace():
    # the parent's serve call: spans, but no parameter-build span
    assert builds.read(_obs({"repro.serve.rebuild": 50})) is None
    assert builds.read(_obs({SPAN: 1}, boundaries=0)) is None
    assert builds.read({**_obs({SPAN: 1}), "counters": {}}) is None
    assert builds.read({"trace": None, "counters":
                        {"traced_boundaries": 50}}) is None


def test_traced_serve_call_builds_once(tiny_cell, tmp_path):
    """One lane, one preset: one build span per call, at boundary 0,
    inside that boundary's ``repro.serve.rebuild``."""
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
        serve._serve(ctx, 2, n, None)
    finally:
        tracer.stop()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in (SPAN, "repro.serve.rebuild"):
                    found.setdefault(e.name, []).append(
                        (dict(e.stats)["boundary"], e.start_ns,
                         e.start_ns + e.duration_ns))
    (k, s, e), = found[SPAN]
    assert k == 0
    rebuild = {b: (lo, hi) for b, lo, hi in found["repro.serve.rebuild"]}
    assert sorted(rebuild) == list(range(n))
    assert rebuild[0][0] <= s and e <= rebuild[0][1]
