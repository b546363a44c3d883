"""Roofline tables: dry-run artifacts + AOT-compiled engine entry points.

Part 1 (``rows``) renders the launch dry-run artifacts under
``experiments/dryrun`` (EXPERIMENTS.md §Roofline). Part 2
(``engine_rows``) is the engine-side roofline this repo actually needs:
AOT-compile ``_run_dyn``/``_run_batch``/``_run_seg_batch`` per
(protocol, T, L), pull FLOPs / bytes-accessed from
``compiled.cost_analysis()`` — the ``lax.while_loop`` body is counted
once, so the numbers are ≈ per engine iteration — and place each
executable against the ``launch/roofline.py`` hardware model
(``dist_to_peak`` = bound-time / compute-time; large = memory-bound).

The entry points are compiled ahead of time for one chip of a described
TPU v5e topology (the TPU compiler ships with JAX; no chip needs to be
attached) and priced with that chip's published peaks
(``launch/roofline.PEAKS``), so the rows mean the same on every host.
"""
import glob
import json
import os
import time

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun")

HEADER = ("arch,shape,mesh,bottleneck,t_compute_ms,t_memory_ms,"
          "t_collective_ms,useful_ratio,mfu_bound,args_gib,temps_gib")

ENGINE_HEADER = ("name,t_bound_us,flops;bytes;ai;bottleneck;dist_to_peak;"
                 "coll_bytes;hlo_kb;compile_s")


def rows(mesh_filter=None, out_dir=None):
    """Dry-run artifact rows; ``mesh_filter`` applies to EVERY row,
    error artifacts included (they carry a mesh too)."""
    out = []
    for f in sorted(glob.glob(os.path.join(out_dir or OUT_DIR, "*.json"))):
        r = json.load(open(f))
        if mesh_filter and r.get("mesh") != mesh_filter:
            continue
        if "error" in r:
            out.append(f"{r['arch']},{r['shape']},{r['mesh']},"
                       f"ERROR,,,,,,,")
            continue
        roof = r["roofline"]
        gb = 1 << 30
        out.append(
            f"{r['arch']},{r['shape']},{r['mesh']},{roof['bottleneck']},"
            f"{roof['t_compute_s'] * 1e3:.2f},"
            f"{roof['t_memory_s'] * 1e3:.2f},"
            f"{roof['t_collective_s'] * 1e3:.2f},"
            f"{roof['useful_ratio']:.3f},{roof['mfu_bound']:.3f},"
            f"{(r['memory']['argument_bytes'] or 0) / gb:.2f},"
            f"{(r['memory']['temp_bytes'] or 0) / gb:.2f}")
    return out


def _cost_totals(compiled) -> tuple[float, float]:
    """(flops, bytes_accessed) from ``cost_analysis`` (dict or [dict])."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def _on(device, tree, lanes=None):
    """Shape-only view of ``tree`` on ``device``, optionally with a
    leading lane axis."""
    import jax
    from jax.sharding import SingleDeviceSharding
    lead = () if lanes is None else (lanes,)
    one = SingleDeviceSharding(device)
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        lead + x.shape, x.dtype, sharding=one), tree)


def _v5e_chip():
    """One chip of a described (not attached) v5e topology."""
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs on disk
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]


def engine_rows(quick=True):
    """AOT roofline rows for the engine entry points, per (protocol,T,L)."""
    import jax
    import jax.numpy as jnp

    from repro.compile_cache import cache_off
    from repro.core.lock import (CostModel, EngineConfig, WorkloadSpec,
                                 protocol_params, split_config,
                                 init_state_dyn)
    from repro.core.lock import engine as E
    from repro.launch.roofline import PEAKS, V5E, collective_bytes
    from repro.obs import compile_log

    chip, peaks = _v5e_chip(), PEAKS[V5E]

    grid = [("mysql", 64, 4), ("brook2pl", 64, 4)]
    if not quick:
        grid += [("mysql", 256, 4), ("group", 256, 4), ("brook2pl", 256, 4)]
    G = 4                       # lanes for the batched entry points

    out = []
    for proto, T, L in grid:
        cfg = EngineConfig(
            protocol=protocol_params(proto), costs=CostModel(),
            workload=WorkloadSpec(kind="hotspot_update", txn_len=L,
                                  n_rows=512),
            n_threads=T, horizon=200_000)
        stat, dp = split_config(cfg)
        s0 = jax.eval_shape(lambda d: init_state_dyn(stat, d), dp)
        until = jax.ShapeDtypeStruct((), jnp.int32)
        entries = [("run_dyn", E._run_dyn,
                    (stat, _on(chip, dp), _on(chip, s0)))]
        # batched + segmented entry points: mysql always; the rest of the
        # grid only in full mode (each AOT compile is seconds on 1 core)
        if proto == "mysql" or not quick:
            entries += [
                ("run_batch", E._run_batch,
                 (stat, _on(chip, dp, G), _on(chip, s0, G))),
                ("run_seg_batch", E._run_seg_batch,
                 (stat, _on(chip, dp, G), _on(chip, s0, G),
                  _on(chip, until, G))),
            ]
        for ename, fn, fargs in entries:
            t0 = time.perf_counter()
            with cache_off():
                compiled = fn.lower(*fargs).compile()
            compile_s = time.perf_counter() - t0
            flops, byts = _cost_totals(compiled)
            hlo = compiled.as_text()
            coll = sum(collective_bytes(hlo).values())
            t_c = flops / peaks.flops
            t_m = byts / peaks.hbm_bw
            t_bound = max(t_c, t_m)
            bottleneck = "compute" if t_c >= t_m else "memory"
            dist = (t_bound / t_c) if t_c > 0 else float("inf")
            out.append(
                f"roofline_engine_{ename}_{proto}_T{T}xL{L},"
                f"{t_bound * 1e6:.4f},"
                f"flops={flops:.0f};bytes={byts:.0f};"
                f"ai={flops / byts if byts else 0.0:.4f};"
                f"bottleneck={bottleneck};"
                f"dist_to_peak={dist if dist != float('inf') else -1:.1f};"
                f"coll_bytes={coll};"
                f"hlo_kb={compile_log.hlo_module_bytes(compiled) / 1024:.1f};"
                f"compile_s={compile_s:.2f}")
    return out


def run(quick=True):
    out = [HEADER] + rows()
    out.append(ENGINE_HEADER)
    out += engine_rows(quick=quick)
    for r in out:
        print(r)
    return out


if __name__ == "__main__":
    run()
