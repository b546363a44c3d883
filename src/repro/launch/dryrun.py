import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(**abstract inputs).compile()`` must succeed
on the production meshes — (16,16) "data","model" and (2,16,16)
"pod","data","model" — for every assigned architecture x input shape.
``memory_analysis()`` proves the per-device fit; ``cost_analysis()`` +
HLO collective parsing feed the roofline table (EXPERIMENTS.md §Roofline).

Usage:
    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
    python -m repro.launch.dryrun --all [--multipod-only|--singlepod-only]
Results are cached as JSON under experiments/dryrun/.
"""

import argparse
import dataclasses
import json
import math
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (get_config, input_specs, SHAPES, shape_grid,
                           ARCHS)
from repro.models import lm_spec, abstract_params
from repro.optim import adamw
from repro.distributed import (param_shardings, batch_shardings,
                               cache_shardings, scalar_sharding,
                               ResolveReport, data_axes)
from repro.distributed.sharding import _axis_size, set_activation_mesh
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (make_train_step, make_prefill_step,
                                make_serve_step)
from repro.launch.roofline import (V5E, collective_bytes, Roofline,
                                   model_flops_estimate,
                                   analytic_hbm_bytes)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# per-arch training recipe overrides: arctic-480b only fits a 256-chip pod
# with bf16 params + blockwise-int8 Adam moments (see DESIGN.md §5/§6).
TRAIN_RECIPE = {
    "arctic-480b": {"param_dtype": jnp.bfloat16, "state_bits": 8},
}

# per-arch config overrides applied to every shape of that arch
ARCH_OVERRIDES = {
    # chunk 128 halves the SSD intra-chunk working set (L and W decay
    # kernels scale with nc*Q^2 = S*Q)
    "mamba2-1.3b": {"ssm_chunk": 128},
}


def _quant_state_shardings(specs, mesh):
    """int8 moments mirror the parameter sharding exactly (q has the param
    shape); per-row scales drop the last axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import resolve_spec, RULES
    from repro.models.common import tree_map_specs

    def f(s):
        ps = resolve_spec(s.shape, s.axes, mesh, RULES["train"])
        entries = list(ps) + [None] * (len(s.shape) - len(list(ps)))
        return {"q": NamedSharding(mesh, P(*entries)),
                "s": NamedSharding(mesh, P(*entries[:-1], None))}
    return tree_map_specs(f, specs)


def _lower_for(cfg, shape, mesh, recipe, report=None):
    """Lower the step function a shape dictates, fully sharded."""
    specs = lm_spec(cfg)
    if shape.step == "train":
        pdt = recipe.get("param_dtype", jnp.float32)
        bits = recipe.get("state_bits", 32)
        rules = recipe.get("rules", "train")
        params = abstract_params(specs, pdt)
        opt = adamw.abstract_state(params, bits)
        p_shard = param_shardings(specs, mesh, rules, report)
        if bits in (32, 16):
            m_shard = p_shard
        else:
            m_shard = _quant_state_shardings(specs, mesh)
        o_shard = adamw.AdamWState(step=scalar_sharding(mesh),
                                   m=m_shard, v=m_shard)
        inputs = input_specs(cfg, shape)
        b_shard = batch_shardings(inputs["batch"], mesh,
                                  batch_dims={"positions3": 1})
        opt_cfg = adamw.AdamWConfig(state_bits=bits)
        fn = make_train_step(cfg, opt_cfg)
        jitted = jax.jit(fn, in_shardings=(p_shard, o_shard, b_shard),
                         donate_argnums=(0, 1))
        return jitted.lower(params, opt, inputs["batch"])
    if shape.step == "prefill":
        params = abstract_params(specs, jnp.bfloat16)
        p_shard = param_shardings(specs, mesh, "serve", report)
        inputs = input_specs(cfg, shape)
        i_shard = batch_shardings(inputs, mesh,
                                  batch_dims={"positions3": 1})
        fn = make_prefill_step(cfg)
        jitted = jax.jit(fn, in_shardings=(p_shard, i_shard))
        return jitted.lower(params, inputs)
    # decode
    params = abstract_params(specs, jnp.bfloat16)
    p_shard = param_shardings(specs, mesh, "serve", report)
    inputs = input_specs(cfg, shape)
    i_shard = dict(caches=cache_shardings(inputs["caches"], mesh),
                   pos=scalar_sharding(mesh))
    for k in ("tokens", "embeds", "positions3"):
        if k in inputs:
            i_shard[k] = batch_shardings(
                {k: inputs[k]}, mesh, batch_dims={"positions3": 1})[k]
    fn = make_serve_step(cfg)
    jitted = jax.jit(fn, in_shardings=(p_shard, i_shard),
                     donate_argnums=(1,))
    return jitted.lower(params, inputs)


def _with_reps(cfg, reps_list):
    layout = tuple((unit, r) for (unit, _), r in zip(cfg.layout, reps_list))
    return dataclasses.replace(cfg, layout=layout)


def corrected_cost(cfg, shape, mesh, recipe):
    """XLA cost_analysis counts while-loop (scan) bodies once; correct by
    linear extrapolation: cost(L) = a + sum_g b_g * reps_g, measured at
    all-reps=1 plus one extra compile per layer group."""
    n_g = len(cfg.layout)
    base_reps = [1] * n_g

    def cost_of(reps):
        # probes unroll layers AND disable the chunked (scan-based) attn/CE
        # paths so no flops hide inside uncounted loop bodies. Probes are
        # only lowered+compiled, never run, so their memory is irrelevant.
        probe_cfg = dataclasses.replace(
            _with_reps(cfg, reps), unroll_layers=True, loss_chunk=0,
            attn_chunk=0)
        low = _lower_for(probe_cfg, shape, mesh, recipe)
        c = low.compile().cost_analysis()
        return (float(c.get("flops", 0.0)),
                float(c.get("bytes accessed", 0.0)))

    f0, b0 = cost_of(base_reps)
    flops, byts = f0, b0
    for g, (_, reps_g) in enumerate(cfg.layout):
        if reps_g == 1:
            continue
        reps = list(base_reps)
        reps[g] = 2
        f1, b1 = cost_of(reps)
        flops += (f1 - f0) * (reps_g - 1)
        byts += (b1 - b0) * (reps_g - 1)
    return flops, byts


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               verbose: bool = True):
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.shape.values())
    cfg = get_config(arch)
    if arch in ARCH_OVERRIDES:
        cfg = dataclasses.replace(cfg, **ARCH_OVERRIDES[arch])
    dp = _axis_size(mesh, data_axes(mesh))
    if cfg.n_experts:
        ds = math.gcd(dp, shape.global_batch)
        cfg = dataclasses.replace(cfg, moe_data_shards=ds)
    if shape.step == "train":
        cfg = dataclasses.replace(cfg, loss_chunk=512)
    if shape.step in ("train", "prefill") and cfg.kinds() & {
            ("global", "dense"), ("global", "moe"),
            ("global", "moe+dense")} or True:
        # query-block chunking keeps per-device score slabs ~<=1 GiB
        dev_b = max(shape.global_batch // dp, 1)
        slab = dev_b * cfg.n_heads * shape.seq_len * 4
        chunk = 512
        while chunk > 64 and slab * chunk > (1 << 30):
            chunk //= 2
        cfg = dataclasses.replace(cfg, attn_chunk=chunk)

    recipe = TRAIN_RECIPE.get(arch, {})
    report = ResolveReport()

    set_activation_mesh(mesh)
    try:
        with mesh:
            lowered = _lower_for(cfg, shape, mesh, recipe, report)
            t0 = time.time()
            compiled = lowered.compile()
            compile_s = time.time() - t0
            flops_c, bytes_c = corrected_cost(cfg, shape, mesh, recipe)
    finally:
        set_activation_mesh(None)

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    reps = max(r for _, r in cfg.layout)
    coll = collective_bytes(hlo, default_trip=reps)

    n_params = cfg.param_count()
    if shape.step == "train":
        pdt = recipe.get("param_dtype", jnp.float32)
        bits = recipe.get("state_bits", 32)
        pbytes = n_params * jnp.dtype(pdt).itemsize
        obytes = n_params * 2 * {32: 4, 16: 2, 8: 1}[bits]
        shards = chips                      # FSDP: fully sharded
    else:
        pbytes = n_params * 2
        obytes = 0
        shards = mesh.shape.get("model", 1)  # serve: TP only
    hbm_bytes = analytic_hbm_bytes(cfg, shape, chips, pbytes, obytes,
                                   param_shards=shards)

    roof = Roofline(
        arch=arch, shape=shape_name,
        mesh="2x16x16" if multi_pod else "16x16",
        chips=chips,
        flops=flops_c,
        bytes_accessed=hbm_bytes,
        coll_bytes=float(sum(coll.values())),
        coll_breakdown=coll,
        model_flops=model_flops_estimate(cfg, shape),
        device_kind=V5E,
    )
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": roof.mesh, "chips": chips,
        "compile_s": compile_s,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                  None),
        },
        "sharding_fallbacks": len(report.fallbacks),
        "hlo_bytes_probe": bytes_c,
        "roofline": roof.row(),
    }
    gb = 1 << 30
    arg = (result["memory"]["argument_bytes"] or 0) / gb
    tmp = (result["memory"]["temp_bytes"] or 0) / gb
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {roof.mesh}: "
              f"compile={compile_s:.1f}s args={arg:.2f}GiB "
              f"temps={tmp:.2f}GiB bottleneck={roof.bottleneck} "
              f"t=({roof.t_compute*1e3:.2f},{roof.t_memory*1e3:.2f},"
              f"{roof.t_collective*1e3:.2f})ms "
              f"useful={roof.useful_ratio:.2f}")
    return result


def cell_path(arch, shape_name, multi_pod):
    mesh = "2x16x16" if multi_pod else "16x16"
    return os.path.join(OUT_DIR, f"{arch}__{shape_name}__{mesh}.json")


def run_cell(arch, shape_name, multi_pod, force=False):
    os.makedirs(OUT_DIR, exist_ok=True)
    path = cell_path(arch, shape_name, multi_pod)
    if os.path.exists(path) and not force:
        with open(path) as f:
            r = json.load(f)
        if "error" not in r:
            print(f"[dryrun] cached: {os.path.basename(path)}")
            return r
    try:
        result = lower_cell(arch, shape_name, multi_pod)
    except Exception as e:
        result = {"arch": arch, "shape": shape_name,
                  "mesh": "2x16x16" if multi_pod else "16x16",
                  "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-2000:]}
        print(f"[dryrun] FAIL {arch} x {shape_name}: {result['error']}")
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=str)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.all:
        ok = fail = 0
        for arch in ARCHS:
            for shape in shape_grid(arch):
                for mp in (False, True):
                    r = run_cell(arch, shape.name, mp, args.force)
                    if "error" in r:
                        fail += 1
                    else:
                        ok += 1
        print(f"[dryrun] {ok} cells OK, {fail} failed")
        raise SystemExit(1 if fail else 0)

    assert args.arch and args.shape
    r = run_cell(args.arch, args.shape, args.multipod, args.force)
    raise SystemExit(1 if "error" in r else 0)


if __name__ == "__main__":
    main()
