"""The trace reduction, on synthetic events and on a recorded TPU trace.

``data/tiny.xplane.pb`` was recorded on one TPU v5 lite: two ``simulate``
calls of group on a 4,096-row hotspot table with 64 threads and a
400-tick horizon, each inside a ``bench.call`` span, inside the
``bench.window`` span (``bench.tracing.Tracer``).
"""
import os

import pytest

from bench import tracing

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


def test_union_merges_overlaps_and_nesting():
    assert tracing._union([(5, 7), (0, 2), (1, 3), (6, 6), (10, 12),
                           (11, 11)]) == [[0, 3], [5, 7], [10, 12]]
    assert tracing._clip([[0, 3], [5, 7]], 2, 6) == [[2, 3], [5, 6]]


def test_self_time_subtracts_nested_children():
    evs = [(0, 10, "while"), (1, 3, "a"), (4, 8, "b"), (5, 6, "c"),
           (12, 14, "a")]
    got = tracing._self_times(evs)
    assert got == {"while": 4, "a": 4, "b": 3, "c": 1}


def test_idle_gap_takes_the_innermost_span():
    spans = [(0, 100, "window"), (10, 50, "call"), (20, 30, "boundary")]
    assert tracing._label(spans, 25) == "boundary"
    assert tracing._label(spans, 40) == "call"
    assert tracing._label(spans, 200) == "none"


def test_op_names_drop_the_hlo_text():
    assert tracing._op_name("%fusion.402 = (s32[64]) fusion(%a), "
                            "kind=kLoop") == "fusion.402"
    assert tracing._op_name("copy-start.3") == "copy-start.3"


@pytest.fixture(scope="module")
def tiny():
    return tracing.reduce(TINY)


def test_recorded_trace_busy_union_and_idle_share(tiny):
    assert list(tiny.busy_s) == [0]
    assert 0 < tiny.busy_s[0] < tiny.window_s
    idle = tiny.idle_s / tiny.window_s
    assert 0 < idle < 1
    assert tiny.mean_busy_s == tiny.busy_s[0]
    # every gap lies inside the window and none is longer than the idle sum
    assert all(0 < s <= tiny.idle_s + 1e-12 for _, s in tiny.idle_gaps)
    assert all(label in ("call", "none") for label, _ in tiny.idle_gaps)


def test_recorded_trace_largest_ops_and_modules(tiny):
    ops = tiny.device_ops
    assert 1 <= len(ops) <= tracing.TOP_N
    secs = [s for _, s in ops]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0
    assert all(" = " not in name for name, _ in ops)
    engine = [n for n in tiny.modules if "_run_dyn" in n]
    assert engine
    # a module's span also holds the short gaps between its operations
    t = sum(tiny.modules[n] for n in engine)
    assert 0.5 * tiny.mean_busy_s < t < tiny.window_s
