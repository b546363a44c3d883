"""Device trace of a run, and its reduction to numbers.

``Tracer`` wraps ``jax.profiler`` around the part of a window a run traces
and marks it with the benchmark's own ``TraceAnnotation`` spans. ``reduce``
reads the ``.xplane.pb`` that the profiler wrote, with JAX alone:

* busy time per device: the union of the intervals in which an operation
  ran on it, inside the traced window;
* the device operations that took most self time (time not covered by an
  operation nested inside them);
* the longest idle gaps, each labelled by the innermost benchmark span
  (``bench.*``) that was open on the host at the gap's midpoint;
* the device time of each XLA module (one compiled program).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP_N = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # length of the traced window
    busy_s: dict                    # device id -> busy seconds in the window
    device_ops: list                # [[op name, self seconds]], largest first
    idle_gaps: list                 # [[host span, seconds]], longest first
    modules: dict                   # module name -> device seconds

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    @property
    def idle_s(self) -> float:
        return self.window_s - self.mean_busy_s


class Tracer:
    """Profiler on between ``start`` and ``stop``; a no-op when off."""

    def __init__(self, on: bool, log_dir: str):
        self.on, self.log_dir = on, log_dir
        self._ann = None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()

    def stop(self) -> None:
        if not self.on or self._ann is None:
            return
        import jax
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()

    def span(self, name: str):
        """A benchmark host span (a no-op context when tracing is off)."""
        if not self.on:
            return _NULL
        import jax
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.log_dir}")
        return found[-1]


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def _union(intervals) -> list:
    """Merge (start, end) intervals; returns the merged, sorted list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(merged, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in merged if e > lo and s < hi]


def _self_times(events) -> dict:
    """Self time per name of properly nested (start, end, name) events."""
    acc = collections.defaultdict(float)
    stack = []          # [end, name, child time]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm, child = stack.pop()
            acc[nm] -= child
        if stack:
            stack[-1][2] += e - s
        acc[name] += e - s
        stack.append([e, name, 0])
    while stack:
        end, nm, child = stack.pop()
        acc[nm] -= child
    return acc


def _op_name(name: str) -> str:
    """``%fusion.402 = (s32[64]...) fusion(...)`` -> ``fusion.402``: the
    device line names an operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def _label(spans, t) -> str:
    """The innermost open span at time t (latest start), or 'none'."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else "none"


def reduce(path: str) -> TraceSummary:
    """Reduce one ``.xplane.pb`` to a :class:`TraceSummary`.

    Times are nanoseconds on the trace's one clock (host and device planes
    are aligned by the profiler). The window is the ``bench.window`` span.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, dev_ops, dev_modules = [], {}, {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns,
                        _op_name(e.name)) for e in line.events]
                if line.name == "XLA Ops":
                    dev_ops[dev] = evs
                elif line.name == "XLA Modules":
                    dev_modules[dev] = evs
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name[len(SPAN_PREFIX):]))
    windows = [(s, e) for s, e, n in spans if SPAN_PREFIX + n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span on the host")
    lo, hi = windows[0]
    inner = [sp for sp in spans if SPAN_PREFIX + sp[2] != WINDOW_SPAN]
    devices = sorted(set(dev_ops) | set(dev_modules))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane with operations")
    busy, gaps, self_t = {}, [], collections.defaultdict(float)
    modules = collections.defaultdict(float)
    for dev in devices:
        source = dev_ops.get(dev) or dev_modules.get(dev, [])
        evs = [(s, e, n) for s, e, n in source if e > lo and s < hi]
        merged = _clip(_union((s, e) for s, e, _ in evs), lo, hi)
        busy[dev] = sum(e - s for s, e in merged) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) / 1e9, _label(inner, (a + b) / 2)))
        for name, t in _self_times(evs).items():
            self_t[name] += t / 1e9
        for s, e, name in dev_modules.get(dev, []):
            if e > lo and s < hi:
                modules[name] += (min(e, hi) - max(s, lo)) / 1e9
    gaps.sort(key=lambda g: -g[0])
    ops = sorted(self_t.items(), key=lambda kv: -kv[1])[:TOP_N]
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=busy,
        device_ops=[[n, t] for n, t in ops],
        idle_gaps=[[label, s] for s, label in gaps[:TOP_N]],
        modules=dict(modules))
