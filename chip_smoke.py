"""Bring-up run of the lock engine on a TPU, at SysBench table scale.

    python chip_smoke.py              # phases 1-4 on one chip
    python chip_smoke.py --chips 4    # sweep lanes sharded over four chips

Two deployments; their shapes are kept and only the simulated horizon is
cut (each cut is printed before the phases run):

* hotspot: SysBench hot-row update on one table of 1,000,000 rows
  (``oltp_common.lua --tables=1 --table-size=1000000``), 1024 client
  threads, one write per transaction.
* zipf: YCSB-style skew over the same 1,000,000 rows, Zipfian constant
  0.99 (YCSB ``ZipfianGenerator``), four writes per transaction, 256
  threads.

Phases, all in this one process and all through the public entry points:

Every timed call is made twice: the first is reported with its compile,
the second as the warm wall (``warm_s``, ``iters_per_s``).

1. ``simulate()`` of mysql, group and brook2pl on the hotspot cell. Checks
   tick conservation and per-record contention conservation, and holds
   mysql and group at 128 threads to the analytic oracle (15%).
2. A drained ``simulate()`` of group on the zipf cell (applied == committed
   on every row at quiescence), and the isolation certifier on a traced run
   of the same cell.
3. ``serve()`` of the hotspot cell under group, open loop, for a few
   segment boundaries (the runner asserts request conservation at each).
4. Backend parity: phase 1's group run at a short horizon on the host CPU
   backend against the chip. Integer state leaves must be equal bit for
   bit; f32 accumulators within a relative tolerance.

``--chips 4`` runs only the multi-chip path: one ``run_sweep`` grid of
protocols x skews on the zipf cell, whose lanes shard over every visible
chip, compared bit for bit with per-point ``simulate()`` on device 0.

Every phase prints one JSON line. The script exits non-zero, without the
final line, when JAX finds no TPU or when any phase fails; it never falls
back to the CPU. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ROWS = 1_000_000                # SysBench --table-size / YCSB record count
HOT_THREADS = 1024
ZIPF_THREADS = 256
ZIPF_S = 0.99
ZIPF_LEN = 4
DEFAULT_HORIZON = 2_000_000     # EngineConfig.horizon: 0.2 s simulated

# simulated-horizon cuts (ticks of 0.1 us), the only cuts this run makes
HOT_HORIZON = 400_000           # phase 1 timed runs
ORACLE_THREADS = 128            # tests/test_lock_engine.py TestOracle
ORACLE_HORIZON = 400_000
DRAIN_HORIZON = 50_000          # phase 2 drained run (drains past it)
CERT_HORIZON = 40_000           # phase 2 traced run
CERT_CAP = 65_536               # trace events kept for the certifier
SERVE_SEG = 50_000              # phase 3 boundary spacing
SERVE_BOUNDARIES = 4
SERVE_RATE = 0.01               # arrivals per tick (100k requests/s)
SERVE_QUEUE = 4096              # admission queue bound
PARITY_HORIZON = 20_000         # phase 4, run on both backends
SWEEP_HORIZON = 20_000          # --chips 4 grid
SWEEP_SKEWS = (0.5, 0.7, 0.8, 0.9, 0.99, 1.1)

F32_RTOL = 1e-6                 # parity tolerance for the f32 accumulators
F32_LEAVES = ("wait_ticks", "busy_ticks", "lat_sum")


def emit(rec: dict) -> None:
    print(json.dumps(rec, sort_keys=True), flush=True)


def platform_of(x) -> str:
    """Platform of the device(s) holding a result array."""
    import jax
    devs = jax.tree.leaves(x)[0].devices()
    return ",".join(sorted({d.platform for d in devs}))


class Phase:
    """Compile seconds and wall time of a phase, from the compile log."""

    def __init__(self):
        from repro.obs import compile_log
        self._log = compile_log
        self._t0 = time.perf_counter()
        self._c0 = compile_log.snapshot()

    def done(self) -> dict:
        d = self._log.delta(self._c0)
        return {"compile_s": d["compile_time_s"],
                "backend_compiles": d["backend_compiles"],
                "wall_s": round(time.perf_counter() - self._t0, 3)}


def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def first_and_warm(fn):
    """Calls ``fn`` twice: the first call (compile included) and a warm
    one. Returns the warm result and both walls."""
    _, first_s = timed(fn)
    out, warm_s = timed(fn)
    return out, first_s, warm_s


def walls(iters, first_s, warm_s) -> dict:
    return {"first_call_s": round(first_s, 3), "warm_s": round(warm_s, 3),
            "iters_per_s": round(iters / warm_s, 1)}


def run_summary(proto, n_threads, s, first_s, warm_s) -> dict:
    from repro.core.lock import extract
    r = extract(proto, n_threads, s)
    return {"protocol": proto, "threads": n_threads,
            "device": platform_of(s), "iters": r.iters,
            "commits": r.commits, "sim_tps": r.tps,
            **walls(r.iters, first_s, warm_s)}


def hot_cell(rows: int):
    from repro.core.lock import WorkloadSpec
    return WorkloadSpec(kind="hotspot_update", n_rows=rows, txn_len=1)


def zipf_cell(rows: int, zipf_s: float = ZIPF_S):
    from repro.core.lock import WorkloadSpec
    return WorkloadSpec(kind="zipf", n_rows=rows, txn_len=ZIPF_LEN,
                        zipf_s=zipf_s)


def phase_engine(rows, threads, horizon, oracle_threads,
                 oracle_horizon) -> dict:
    """Phase 1: the three protocol families on the hotspot cell."""
    from repro.core.lock import CostModel, extract, simulate
    from repro.core.lock.ref_engine import predicted_tps
    from repro.obs import check_ca_conservation, check_conservation
    ph, cell, runs = Phase(), hot_cell(rows), []
    for proto in ("mysql", "group", "brook2pl"):
        def run():
            return simulate(proto, cell, threads, horizon=horizon,
                            attrib=True)
        s, first_s, warm_s = first_and_warm(run)
        check_conservation(s, threads)
        check_ca_conservation(s)
        runs.append(run_summary(proto, threads, s, first_s, warm_s))
    oracle = []
    for proto in ("mysql", "group"):
        s, first_s, warm_s = first_and_warm(lambda: simulate(
            proto, cell, oracle_threads, horizon=oracle_horizon))
        r = extract(proto, oracle_threads, s)
        got = r.tps
        want = predicted_tps(proto, oracle_threads, CostModel())
        rel = abs(got - want) / want
        oracle.append({"protocol": proto, "threads": oracle_threads,
                       "sim_tps": got, "predicted_tps": want,
                       "rel_err": round(rel, 4), "iters": r.iters,
                       **walls(r.iters, first_s, warm_s)})
        if rel > 0.15:
            raise AssertionError(f"{proto} at T={oracle_threads}: "
                                 f"{got:.0f} tps vs oracle {want:.0f}")
    return {"runs": runs, "oracle": oracle, **ph.done()}


def phase_drain(rows, threads, horizon, cert_horizon, cert_cap) -> dict:
    """Phase 2: counter invariant at quiescence + certified schedule."""
    import numpy as np
    from repro.analysis.isolation import certify_run
    from repro.core.lock import HALT, simulate
    from repro.obs import check_conservation
    ph, cell = Phase(), zipf_cell(rows)
    s, first_s, warm_s = first_and_warm(lambda: simulate(
        "group", cell, threads, horizon=horizon, drain=True))
    check_conservation(s, threads)
    phase, ticket = np.asarray(s.th.phase), np.asarray(s.th.ticket)
    applied = np.asarray(s.rows.applied_val)
    committed = np.asarray(s.rows.committed_val)
    if not (phase == HALT).all() or (ticket >= 0).any():
        raise AssertionError("drained run did not quiesce")
    leftover = int(np.abs(applied.astype(np.int64) - committed).sum())
    if leftover or int(s.g.commits) == 0:
        raise AssertionError(f"applied != committed by {leftover} "
                             f"({int(s.g.commits)} commits)")
    cert, cert_first_s, cert_warm_s = first_and_warm(lambda: certify_run(
        "group", cell, threads, horizon=cert_horizon, cap=cert_cap))
    if not cert.serializable or cert.lower_bound or cert.violations:
        raise AssertionError(f"certifier: {cert}")
    return {"device": platform_of(s), "iters": int(s.g.iters),
            "commits": int(s.g.commits), "drained_at": int(s.g.now),
            "applied_total": int(applied.sum()),
            **walls(int(s.g.iters), first_s, warm_s),
            "cert": {"serializable": cert.serializable,
                     "attempts": cert.n_attempts,
                     "committed": cert.n_committed, "edges": cert.n_edges,
                     "first_call_s": round(cert_first_s, 3),
                     "warm_s": round(cert_warm_s, 3)},
            **ph.done()}


def phase_serve(rows, threads, seg, boundaries, rate) -> dict:
    """Phase 3: open-loop serving of the hotspot cell under group."""
    from repro.serving import ServeCell, poisson, serve
    ph = Phase()
    horizon = seg * boundaries
    cell = ServeCell(name="hotspot_group", schedule=poisson(rate, horizon),
                     workload=hot_cell(rows), n_threads=threads,
                     preset="group", queue_cap=SERVE_QUEUE)
    res, first_s, warm_s = first_and_warm(
        lambda: serve([cell], seg_ticks=seg, return_states=True))
    sr, st = res.serving[cell.name], res.states[cell.name]
    if sr.completed <= 0:
        raise AssertionError(f"serve completed nothing: {sr}")
    if len(res.segments[cell.name]) != boundaries:
        raise AssertionError("serve skipped a boundary")
    return {"device": platform_of(st), "iters": int(st.g.iters),
            "commits": int(st.g.commits),
            **walls(int(st.g.iters), first_s, warm_s),
            "boundaries": boundaries, "arrived": sr.arrived,
            "completed": sr.completed, "rejected": sr.rejected,
            "goodput_tps": sr.goodput_tps, "p99_us": sr.p99_us,
            "warm_compiles": res.n_compiles, **ph.done()}


def phase_parity(rows, threads, horizon) -> dict:
    """Phase 4: the same run on the chip and on the host CPU backend."""
    import jax
    import numpy as np
    from repro.core.lock import simulate
    ph, cell = Phase(), hot_cell(rows)

    def run():
        return simulate("group", cell, threads, horizon=horizon,
                        attrib=True)
    s_chip, first_s, warm_s = first_and_warm(run)
    chip_platform = platform_of(s_chip)
    chip = jax.device_get(s_chip)
    with jax.default_device(jax.devices("cpu")[0]):
        s_cpu, cpu_s = timed(run)
        cpu_platform = platform_of(s_cpu)
        host = jax.device_get(s_cpu)
    if cpu_platform != "cpu":
        raise AssertionError(f"reference ran on {cpu_platform}")
    int_diff, f32 = [], {}
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(chip)[0],
            jax.tree_util.tree_flatten_with_path(host)[0]):
        a, b = np.asarray(a), np.asarray(b)
        name = jax.tree_util.keystr(path)
        if a.dtype.kind == "f":
            rel = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))
            f32[name] = rel
        elif not np.array_equal(a, b):
            int_diff.append(f"{name}: {int((a != b).sum())} of {a.size}")
    bad_f32 = {k: v for k, v in f32.items() if v > F32_RTOL}
    out = {"device": chip_platform, "reference_device": cpu_platform,
           "iters": int(chip.g.iters), "commits": int(chip.g.commits),
           **walls(int(chip.g.iters), first_s, warm_s),
           "reference_first_call_s": round(cpu_s, 3),
           "int_leaves_differing": int_diff, "f32_max_rel": f32,
           "f32_rtol": F32_RTOL, **ph.done()}
    if int_diff or bad_f32 or set(f32) != {f".g.{n}" for n in F32_LEAVES}:
        raise AssertionError(f"backend parity: {out}")
    return out


class _DeviceSpy:
    """Wraps ``engine._run_batch`` to record how many devices hold each
    packed output: the lanes must really be spread over the chips."""

    def __init__(self, fn):
        self.fn, self.n_devices = fn, []

    def __call__(self, *args):
        out = self.fn(*args)
        self.n_devices.append(len(out.g.now.sharding.device_set))
        return out

    def _cache_size(self):
        return self.fn._cache_size()


def phase_sweep(rows, threads, horizon, skews) -> dict:
    """--chips 4: sharded sweep lanes vs per-point simulate() on device 0."""
    import jax
    from repro.core.lock import PROTOCOLS, engine, extract, simulate
    from repro.sweep import grid, run_sweep
    from repro.sweep.grid import expand
    ph, n_dev = Phase(), len(jax.devices())
    pts = grid(list(PROTOCOLS), expand(zipf_cell(rows), zipf_s=list(skews)),
               threads, horizon=horizon)
    spy = _DeviceSpy(engine._run_batch)
    engine._run_batch = spy
    try:
        res = run_sweep(pts)
    finally:
        engine._run_batch = spy.fn
    sweep_s = res.wall_s
    if not spy.n_devices or min(spy.n_devices) != n_dev:
        raise AssertionError(f"lanes on {spy.n_devices} devices, "
                             f"{n_dev} visible")
    t0 = time.perf_counter()
    mismatched = []
    with jax.default_device(jax.devices()[0]):
        for p in pts:
            ref = extract(p.protocol, p.n_threads, simulate(
                p.protocol, p.workload, p.n_threads, horizon=p.horizon))
            got = res[p.name]
            if got != ref:
                mismatched.append(p.name + ": " + ", ".join(
                    f.name for f in dataclasses.fields(ref)
                    if getattr(got, f.name) != getattr(ref, f.name)))
    out = {"points": len(pts), "devices": n_dev,
           "lane_devices": sorted(set(spy.n_devices)),
           "sweep_s": round(sweep_s, 3),
           "reference_s": round(time.perf_counter() - t0, 3),
           "sweep_compiles": res.n_compiles, "lane_iters": res.lane_iters,
           "repacks": res.n_repacks, "device_calls": len(spy.n_devices),
           "mismatched": mismatched, **ph.done()}
    if mismatched:
        raise AssertionError(f"sweep parity: {out}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sweep sharded over four chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {device})",
              file=sys.stderr)
        return 1
    if args.chips == 4 and len(devs) != 4:
        print(f"chip_smoke: --chips 4 needs four chips, JAX sees {device}",
              file=sys.stderr)
        return 1
    emit({"device": device, "cache_dir": cache_dir, "jax": jax.__version__})

    if args.chips == 4:
        emit({"cut": "sweep horizon", "ticks": SWEEP_HORIZON,
              "default_ticks": DEFAULT_HORIZON})
        phases = [("sweep_4chip", lambda: phase_sweep(
            ROWS, ZIPF_THREADS, SWEEP_HORIZON, SWEEP_SKEWS))]
    else:
        for name, ticks in (("hotspot horizon", HOT_HORIZON),
                            ("oracle horizon", ORACLE_HORIZON),
                            ("zipf drain horizon", DRAIN_HORIZON),
                            ("certifier horizon", CERT_HORIZON),
                            ("serve horizon", SERVE_SEG * SERVE_BOUNDARIES),
                            ("parity horizon", PARITY_HORIZON)):
            emit({"cut": name, "ticks": ticks,
                  "default_ticks": DEFAULT_HORIZON})
        phases = [
            ("1_engine", lambda: phase_engine(
                ROWS, HOT_THREADS, HOT_HORIZON, ORACLE_THREADS,
                ORACLE_HORIZON)),
            ("2_drain_certify", lambda: phase_drain(
                ROWS, ZIPF_THREADS, DRAIN_HORIZON, CERT_HORIZON, CERT_CAP)),
            ("3_serve", lambda: phase_serve(
                ROWS, HOT_THREADS, SERVE_SEG, SERVE_BOUNDARIES, SERVE_RATE)),
            ("4_parity", lambda: phase_parity(
                ROWS, HOT_THREADS, PARITY_HORIZON)),
        ]
    failed = []
    for name, fn in phases:
        try:
            emit({"phase": name, "ok": True, **fn()})
        except Exception as e:  # report every phase, then fail the run
            traceback.print_exc()
            emit({"phase": name, "ok": False,
                  "error": f"{type(e).__name__}: {e}"[:2000]})
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
