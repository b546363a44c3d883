"""What the program marks in a traced run, read off the run's own trace.

``bench.tracing.reduce`` gives the benchmark's numbers of the traced
window. This module reads the same ``.xplane.pb``, on the same clock, for
what the program itself labels there:

* device self time of every operation, keyed by (XLA module, HLO
  instruction). The engine's stage map (``repro.obs.prof.stage_map`` of
  the engine entry the cell ran, built after the window) turns it into
  device time per ``engine.STAGE_SCOPES`` scope;
* the program's host spans, the ``jax.profiler.TraceAnnotation`` events
  named ``repro.*`` (``repro.serve.*`` per serve boundary,
  ``repro.engine.*`` per ``run_sim`` call): the self time of each name
  (its duration less what nested ``repro.*`` spans cover), and the
  device-idle time split among the innermost ``repro.*`` span open over
  it, by exact intersection with the merged idle intervals; idle time
  under no program span is ``none``.

``bench.*`` spans, and with them ``reduce``'s ``idle_gaps``, are not read
here. A program without these scopes or spans (the parent of the change
that added them) gives None, and the readers leave their metric out.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import os

from bench import calls, spec, tracing

PROGRAM_PREFIX = "repro."
NONE = "none"
TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")   # bench/run.py's
# the engine entry each driver runs its calls through
ENTRY = {"closed": "_run_dyn", "serve": "_run_seg_dyn"}
# a stage map that misses more of the engine's op time came from another
# executable than the one that ran
MAX_MISSING = 0.01


@dataclasses.dataclass
class ProgramTrace:
    op_self: dict       # (module, op) -> device self seconds, all devices
    span_self: dict     # repro.* span name -> host self seconds
    span_count: dict    # repro.* span name -> spans in the window
    idle_split: dict    # innermost repro.* span | "none" -> idle seconds,
    #                     mean over devices (sums to TraceSummary.idle_s)


def _module_of(modules) -> callable:
    """op start -> name of the module event that holds it ('' if none)."""
    mods = sorted(modules)
    starts = [s for s, _, _ in mods]

    def find(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and mods[i][0] <= t < mods[i][1]:
            return mods[i][2]
        return ""
    return find


def _innermost(spans) -> list:
    """[[start, end, name]]: the timeline cut where any span starts or
    ends, each piece named by the innermost span open over it (latest
    start), pieces under no span left out. Sorted, not overlapping."""
    points = sorted({t for s, e, _ in spans for t in (s, e)})
    out = []
    for a, b in zip(points, points[1:]):
        best = None
        for s, e, name in spans:
            if s <= a and b <= e and (best is None or s >= best[0]):
                best = (s, name)
        if best is not None:
            out.append([a, b, best[1]])
    return out


def split_idle(gaps, spans) -> dict:
    """Split idle intervals ``gaps`` (sorted, not overlapping) among the
    innermost of ``spans`` open over each instant; the rest is 'none'.
    Exact: the parts sum to the gaps' total."""
    pieces = _innermost(spans)
    acc = collections.defaultdict(int)
    i = 0
    for a, b in gaps:
        covered = 0
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            s, e, name = pieces[j]
            d = min(b, e) - max(a, s)
            if d > 0:
                acc[name] += d
                covered += d
            j += 1
        acc[NONE] += (b - a) - covered
    return dict(acc)


_READ: dict = {}


def read_file(path: str) -> ProgramTrace:
    """Reduce one ``.xplane.pb`` to a :class:`ProgramTrace` (once per
    file as written: a traced run's file holds millions of events)."""
    st = os.stat(path)
    key = (os.path.realpath(path), st.st_mtime_ns, st.st_size)
    if key not in _READ:
        _READ.clear()
        _READ[key] = _read(path)
    return _READ[key]


def _read(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, window, dev_ops, dev_modules = [], None, {}, {}
    for plane in pd.planes:
        m = tracing.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                evs = [(e.start_ns, e.start_ns + e.duration_ns,
                        tracing._op_name(e.name)) for e in line.events]
                if line.name == "XLA Ops":
                    dev_ops[int(m.group(1))] = evs
                elif line.name == "XLA Modules":
                    dev_modules[int(m.group(1))] = evs
                continue
            for e in line.events:
                iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                if e.name.startswith(PROGRAM_PREFIX):
                    spans.append(iv)
                elif e.name == tracing.WINDOW_SPAN and window is None:
                    window = iv[:2]
    if window is None:
        raise ValueError(f"{path}: no {tracing.WINDOW_SPAN} span")
    lo, hi = window
    spans = [sp for sp in spans if sp[1] > lo and sp[0] < hi]
    devices = sorted(set(dev_ops) | set(dev_modules))
    op_self = collections.defaultdict(float)
    idle = collections.defaultdict(int)
    for dev in devices:
        source = dev_ops.get(dev) or dev_modules.get(dev, [])
        evs = [(s, e, n) for s, e, n in source if e > lo and s < hi]
        merged = tracing._clip(tracing._union((s, e) for s, e, _ in evs),
                               lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for name, t in split_idle(gaps, spans).items():
            idle[name] += t
        module = _module_of(dev_modules.get(dev, []))
        keyed = [(s, e, (module(s), n)) for s, e, n in evs]
        for key, t in tracing._self_times(keyed).items():
            op_self[key] += t / 1e9
    n_dev = max(len(devices), 1)
    return ProgramTrace(
        op_self=dict(op_self),
        span_self={n: t / 1e9
                   for n, t in tracing._self_times(spans).items()},
        span_count=dict(collections.Counter(n for _, _, n in spans)),
        idle_split={n: t / 1e9 / n_dev for n, t in idle.items()})


def of(obs) -> ProgramTrace | None:
    """The run's :class:`ProgramTrace` (read once per run, kept in
    ``obs``), or None without a trace. The file must be the one
    ``obs["trace"]`` was reduced from: same window."""
    if "program_trace" not in obs:
        obs["program_trace"] = None
        tr = obs.get("trace")
        if tr is not None:
            try:
                pt = read_file(tracing.Tracer(True, TRACE_DIR).xplane())
            except FileNotFoundError:
                return None
            n_dev = len(tr.busy_s)
            if abs(sum(pt.idle_split.values()) - tr.idle_s) <= 1e-9 * n_dev:
                obs["program_trace"] = pt
    return obs["program_trace"]


# --- the stage map of the engine entry a cell ran -------------------------

class _Reached(Exception):
    def __init__(self, args):
        super().__init__()
        self.call_args = args


class _StopAt:
    """Stands in for a jitted entry: a call raises :class:`_Reached` with
    its arguments; anything else (``_cache_size``) is the entry's."""

    def __init__(self, real):
        self.real = real

    def __call__(self, *args):
        raise _Reached(args)

    def __getattr__(self, name):
        return getattr(self.real, name)


def _first_call(cell) -> None:
    """Start one call the way the cell's driver does."""
    from bench.drivers import closed, serve
    ctx = calls.Context(cell=cell, seed=0, seconds=0.0,
                        tracer=tracing.Tracer(False, TRACE_DIR), t_start=0.0)
    seed = calls.call_seed(0, 0)
    if cell.driver == "closed":
        closed._simulate(ctx, seed, int(cell.config["horizon"]))
    else:
        serve._serve(ctx, seed, int(cell.traffic["boundaries"]), None)


def entry_args(cell):
    """(jitted entry, its arguments) of a call of ``cell``: the driver's
    own call path, stopped where it reaches the engine entry, so the
    arguments are those the window's calls passed."""
    from repro.core.lock import engine
    name = ENTRY[cell.driver]
    real = getattr(engine, name)
    setattr(engine, name, _StopAt(real))
    try:
        _first_call(cell)
    except _Reached as r:
        return real, r.call_args
    finally:
        setattr(engine, name, real)
    raise RuntimeError(f"{cell.name}: the call never reached {name}")


def _cell_of(metric: str):
    """The one cell ``metric`` is listed for in BENCHMARK.json."""
    entry = {m["name"]: m for m in spec.benchmark()["per_layer"]}[metric]
    cells = entry.get("workloads", [])
    return spec.load_cell(cells[0]) if len(cells) == 1 else None


def op_stages(obs, metric: str) -> dict | None:
    """``{op: stage}`` of the engine executable the cell of ``metric``
    ran, built once per run after the window (kept in ``obs``); None
    where the program has no stage map."""
    if "op_stages" not in obs:
        obs["op_stages"] = None
        from repro.obs import prof
        stage_map = getattr(prof, "stage_map", None)
        cell = _cell_of(metric)
        if stage_map is not None and cell is not None:
            jitted, args = entry_args(cell)
            obs["op_stages"] = stage_map(jitted, *args)
    return obs["op_stages"]


def stage_seconds(obs, metric: str) -> dict | None:
    """``{stage: device seconds}`` of the ops of the engine modules
    (``calls.ENGINE_ENTRIES``) in the traced window; None without a trace
    or a map, or where the map misses over 1% of that op time."""
    if "stage_seconds" in obs:
        return obs["stage_seconds"]
    obs["stage_seconds"] = None
    pt = of(obs)
    if pt is None:
        return None
    ops = {k: t for k, t in pt.op_self.items()
           if any(p in k[0] for p in calls.ENGINE_ENTRIES)}
    total = sum(ops.values())
    stages = op_stages(obs, metric) if total > 0 else None
    if stages is None:
        return None
    missing = sum(t for (_, op), t in ops.items() if op not in stages)
    if missing > MAX_MISSING * total:
        return None
    acc = collections.defaultdict(float)
    for (_, op), t in ops.items():
        acc[stages.get(op)] += t
    obs["stage_seconds"] = dict(acc)
    return obs["stage_seconds"]


def stage_us_per_iter(obs, metric: str, scopes) -> float | None:
    """Device time of ``scopes`` per engine loop iteration, in us."""
    iters = obs["counters"].get("traced_iters", 0)
    secs = stage_seconds(obs, metric) if iters else None
    if secs is None:
        return None
    return 1e6 * sum(secs.get(s, 0.0) for s in scopes) / iters


def per_boundary_ms(obs, seconds) -> float | None:
    """``seconds`` of the traced serve call per boundary, in ms."""
    n = obs["counters"].get("traced_boundaries", 0)
    if seconds is None or not n:
        return None
    return 1e3 * seconds / n


def span_self(obs, name: str) -> float | None:
    """Host self seconds of the ``name`` spans, None where none ran."""
    pt = of(obs)
    if pt is None or name not in pt.span_self:
        return None
    return pt.span_self[name]
