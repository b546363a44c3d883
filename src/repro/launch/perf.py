import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

"""Perf-iteration harness (§Perf): lower a cell with config/recipe
overrides and report the three roofline terms, so each
hypothesis->change->measure cycle is one CLI invocation.

    python -m repro.launch.perf --arch qwen2-0.5b --shape train_4k \
        --set rules=train_dp
    python -m repro.launch.perf --arch deepseek-coder-33b \
        --shape decode_32k --set kv_dtype=float8_e4m3fn
"""

import argparse
import dataclasses
import json
import math

import jax
import jax.numpy as jnp

from repro.configs import get_config, SHAPES
from repro.distributed import ResolveReport, data_axes
from repro.distributed.sharding import _axis_size, set_activation_mesh
from repro.launch.mesh import make_production_mesh
from repro.launch import dryrun as dr
from repro.launch.roofline import (V5E, collective_bytes, Roofline,
                                   model_flops_estimate,
                                   analytic_hbm_bytes)

CFG_KEYS = {"kv_dtype", "attn_chunk", "loss_chunk", "capacity_factor",
            "act_dtype", "remat", "moe_data_shards", "ssm_chunk", "window"}
RECIPE_KEYS = {"rules", "state_bits", "param_dtype"}


def run_variant(arch, shape_name, multi_pod, overrides, tag):
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.shape.values())
    cfg = get_config(arch)
    dp = _axis_size(mesh, data_axes(mesh))
    if cfg.n_experts:
        cfg = dataclasses.replace(
            cfg, moe_data_shards=math.gcd(dp, shape.global_batch))
    if shape.step == "train":
        cfg = dataclasses.replace(cfg, loss_chunk=512)
    dev_b = max(shape.global_batch // dp, 1)
    slab = dev_b * cfg.n_heads * shape.seq_len * 4
    chunk = 512
    while chunk > 64 and slab * chunk > (1 << 30):
        chunk //= 2
    cfg = dataclasses.replace(cfg, attn_chunk=chunk)

    recipe = dict(dr.TRAIN_RECIPE.get(arch, {}))
    cfg_over = {}
    for k, v in overrides.items():
        if k in RECIPE_KEYS:
            recipe[k] = (jnp.bfloat16 if v == "bfloat16" else
                         jnp.float32 if v == "float32" else
                         int(v) if k == "state_bits" else v)
        elif k in CFG_KEYS:
            field = ModelConfigField(k)
            cfg_over[k] = field(v)
        else:
            raise KeyError(k)
    if cfg_over:
        cfg = dataclasses.replace(cfg, **cfg_over)

    report = ResolveReport()
    set_activation_mesh(mesh)
    try:
        with mesh:
            lowered = dr._lower_for(cfg, shape, mesh, recipe, report)
            compiled = lowered.compile()
            flops_c, bytes_probe = dr.corrected_cost(cfg, shape, mesh,
                                                     recipe)
    finally:
        set_activation_mesh(None)
    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text(),
                            default_trip=max(r for _, r in cfg.layout))
    n_params = cfg.param_count()
    if shape.step == "train":
        pdt = recipe.get("param_dtype", jnp.float32)
        bits = recipe.get("state_bits", 32)
        pbytes = n_params * jnp.dtype(pdt).itemsize
        obytes = n_params * 2 * {32: 4, 16: 2, 8: 1}[bits]
        shards = chips
    else:
        pbytes, obytes = n_params * 2, 0
        shards = mesh.shape.get("model", 1)
    roof = Roofline(
        arch=arch, shape=shape_name,
        mesh="2x16x16" if multi_pod else "16x16", chips=chips,
        flops=flops_c,
        bytes_accessed=analytic_hbm_bytes(cfg, shape, chips, pbytes,
                                          obytes, param_shards=shards),
        coll_bytes=float(sum(coll.values())), coll_breakdown=coll,
        model_flops=model_flops_estimate(cfg, shape),
        device_kind=V5E)
    gb = 1 << 30
    print(f"[perf:{tag}] {arch} x {shape_name} x {roof.mesh}: "
          f"t_comp={roof.t_compute*1e3:.2f}ms "
          f"t_mem={roof.t_memory*1e3:.2f}ms "
          f"t_coll={roof.t_collective*1e3:.2f}ms "
          f"bottleneck={roof.bottleneck} "
          f"temps={(mem.temp_size_in_bytes or 0)/gb:.2f}GiB "
          f"mfu_bound={roof.mfu_bound:.3f} "
          f"coll={ {k: round(v/gb, 2) for k, v in coll.items() if v} }")
    return roof


def ModelConfigField(k):
    casts = {"attn_chunk": int, "loss_chunk": int, "moe_data_shards": int,
             "ssm_chunk": int, "window": int, "capacity_factor": float,
             "remat": lambda v: v in ("1", "true", "True")}
    return casts.get(k, str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="key=value override (cfg or recipe)")
    ap.add_argument("--tag", default="variant")
    args = ap.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.set)
    run_variant(args.arch, args.shape, args.multipod, overrides, args.tag)


if __name__ == "__main__":
    main()
