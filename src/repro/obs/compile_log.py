"""Shared compile accounting across the repo's jitted entry points.

Three layers, all opt-in and zero-cost when unused:

* **Cache counting** (``total_compiles()``) — sum of jit-cache sizes over
  every registered entry point. ``benchmarks/run.py`` records per-module
  deltas into BENCH_run.json so a recompile regression (a param
  accidentally promoted into the compile key) shows up on the perf
  trajectory instead of as mysterious wall time.
* **Compile telemetry** (``enable_telemetry``/``snapshot``/``delta``) —
  wall time actually spent in XLA backend compilation, counted via
  ``jax.monitoring`` duration events, plus per-function attribution
  parsed from jax's own "Finished XLA compilation of jit(name)" log line
  (captured silently at DEBUG level — nothing is printed). BENCH_run.json
  carries the per-module ``compile_time_s`` next to ``compiles``.
* **Strict cross-check** (``REPRO_COMPILE_STRICT=1``) — ``total_compiles``
  silently undercounts when a subsystem forgets to ``register()`` its
  jitted entry point; strict mode sweeps the heap for live repo-owned
  jit wrappers with non-empty caches that the accounting doesn't know
  about and warns with their names.

Subsystems with their own jitted entry points register them here
(idempotent, as ``serving/runner.py`` does for ``_hist_add``); the core
engine/aria/obs/kernels entry points are built in. The analysis
linter (``repro.analysis.jaxpr_lint``) keeps its entry-point registry
mirrored against ``_jitted()``.
"""
from __future__ import annotations

import logging
import os
import re

_EXTRA: list = []


def register(fn) -> None:
    """Add a jitted function to the global compile accounting."""
    if fn not in _EXTRA:
        _EXTRA.append(fn)


def _jitted() -> list:
    # imported lazily: this module must stay importable before jax warms up
    from repro.core.lock import aria, engine
    from repro.obs import trace
    fns = [
        engine._run_dyn, engine._run_batch,
        engine._run_seg_dyn, engine._run_seg_batch,
        aria._run_dyn, aria._run_batch,
        aria._run_seg_dyn, aria._run_seg_batch,
        trace._run_traced,
    ]
    from repro.kernels.grouped_scatter import kernel as gk, ops as go
    fns += [go.grouped_scatter_apply, gk.segment_sums]
    return fns + list(_EXTRA)


def total_compiles() -> int:
    """Sum of jit-cache sizes over every registered entry point.

    With ``REPRO_COMPILE_STRICT=1`` also cross-checks the registry
    against every live repo-owned jit wrapper on the heap and warns
    (once per function) about any with compiles the sum missed.
    """
    total = 0
    for fn in _jitted():
        try:
            total += int(fn._cache_size())
        except Exception:      # cache API unavailable: count what we can
            pass
    if os.environ.get("REPRO_COMPILE_STRICT") == "1":
        strict_check()
    return total


# ---------------------------------------------------------------------------
# strict mode: find jitted repo functions the accounting doesn't know about
# ---------------------------------------------------------------------------

_STRICT_WARNED: set[str] = set()


def _owner_module(wrapper) -> str:
    wrapped = getattr(wrapper, "__wrapped__", None)
    return getattr(wrapped, "__module__", None) or ""


def unregistered_compiles(prefixes=("repro.", "benchmarks")) -> list[str]:
    """Names of live repo-owned jit wrappers with cached executables that
    ``total_compiles()`` is not counting. Heap sweep — call sparingly."""
    import gc
    known = {id(fn) for fn in _jitted()}
    out = []
    for obj in gc.get_objects():
        try:
            if not (hasattr(obj, "_cache_size") and hasattr(obj, "__wrapped__")):
                continue
            if id(obj) in known:
                continue
            mod = _owner_module(obj)
            if not mod.startswith(prefixes):
                continue
            if int(obj._cache_size()) > 0:
                out.append(f"{mod}.{getattr(obj, '__name__', repr(obj))}")
        except Exception:
            continue
    return sorted(set(out))


def strict_check(warn=None) -> list[str]:
    """Warn (once per name) about unregistered compiled entry points."""
    missing = unregistered_compiles()
    fresh = [m for m in missing if m not in _STRICT_WARNED]
    _STRICT_WARNED.update(fresh)
    for name in fresh:
        msg = (f"compile_log: unregistered jitted entry point with "
               f"compiled executables: {name} — total_compiles() is "
               f"undercounting; register() it")
        (warn or logging.getLogger(__name__).warning)(msg)
    return missing


# ---------------------------------------------------------------------------
# compile telemetry: wall time in XLA, per-function where attributable
# ---------------------------------------------------------------------------

_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_FINISHED_RE = re.compile(
    r"Finished XLA compilation of (?P<name>.+?) in (?P<secs>[0-9.eE+-]+) sec")

_TELE = {
    "enabled": False,
    "compile_time_s": 0.0,      # total secs in XLA backend compilation
    "backend_compiles": 0,      # number of backend compile events
    "fns": {},                  # "jit(name)" -> {"n": int, "secs": float}
}


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _BACKEND_EVENT:
        _TELE["compile_time_s"] += float(duration)
        _TELE["backend_compiles"] += 1


class _FinishedHandler(logging.Handler):
    """Silently harvests per-function compile times from jax's own
    'Finished XLA compilation of jit(name) in S sec' debug line.

    Capture requires the dispatch logger at DEBUG with propagation off
    (else every debug line sprays stderr); records at INFO and above are
    re-dispatched to the root logger so real warnings still surface.
    """

    def emit(self, record: logging.LogRecord) -> None:
        try:
            if record.levelno > logging.DEBUG:
                logging.getLogger().handle(record)
            m = _FINISHED_RE.search(record.getMessage())
        except Exception:
            return
        if not m:
            return
        rec = _TELE["fns"].setdefault(m.group("name"), {"n": 0, "secs": 0.0})
        rec["n"] += 1
        rec["secs"] += float(m.group("secs"))


def enable_telemetry() -> bool:
    """Start recording compile wall time. Idempotent; returns enabled.

    Uses ``jax.monitoring`` duration events for totals (authoritative)
    and a DEBUG-level log capture on ``jax._src.dispatch`` for per-name
    attribution (best effort — the log line is jax-internal and absent
    on cache hits from the persistent compilation cache).
    """
    if _TELE["enabled"]:
        return True
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:
        return False
    try:
        lg = logging.getLogger("jax._src.dispatch")
        lg.setLevel(logging.DEBUG)
        lg.propagate = False
        lg.addHandler(_FinishedHandler(level=logging.DEBUG))
    except Exception:
        pass        # totals still work without per-name attribution
    _TELE["enabled"] = True
    return True


def snapshot() -> dict:
    """Current telemetry counters (enables telemetry on first use)."""
    enable_telemetry()
    return {
        "compile_time_s": _TELE["compile_time_s"],
        "backend_compiles": _TELE["backend_compiles"],
        "fns": {k: dict(v) for k, v in _TELE["fns"].items()},
        "compiles": total_compiles(),
    }


def delta(prev: dict) -> dict:
    """Telemetry delta since a previous :func:`snapshot`."""
    cur = snapshot()
    fns = {}
    for name, rec in cur["fns"].items():
        p = prev.get("fns", {}).get(name, {"n": 0, "secs": 0.0})
        dn, ds = rec["n"] - p["n"], rec["secs"] - p["secs"]
        if dn or ds > 1e-9:
            fns[name] = {"n": dn, "secs": round(ds, 4)}
    return {
        "compile_time_s": round(
            cur["compile_time_s"] - prev.get("compile_time_s", 0.0), 4),
        "backend_compiles":
            cur["backend_compiles"] - prev.get("backend_compiles", 0),
        "fns": fns,
        "compiles": cur["compiles"] - prev.get("compiles", 0),
    }


def hlo_module_bytes(compiled) -> int:
    """Size of a compiled executable's optimized HLO text, in bytes.

    Takes anything with ``as_text()`` (``jax.stages.Compiled`` or
    ``Lowered``); 0 when the backend can't render it.
    """
    try:
        return len(compiled.as_text().encode())
    except Exception:
        return 0
