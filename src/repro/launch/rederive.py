"""Recompute roofline terms in cached dry-run JSONs after analytic-model
changes (FLOPs and collective bytes come from the stored compile results;
only the memory term and derived fields are re-derived)."""
import dataclasses
import glob
import json
import math
import os
import sys

import jax.numpy as jnp

from repro.configs import get_config, SHAPES
from repro.launch.roofline import (V5E, Roofline, analytic_hbm_bytes,
                                   model_flops_estimate)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")
TRAIN_RECIPE = {"arctic-480b": {"param_dtype": jnp.bfloat16,
                                "state_bits": 8}}
ARCH_OVERRIDES = {"mamba2-1.3b": {"ssm_chunk": 128}}


def main():
    for path in sorted(glob.glob(os.path.join(OUT_DIR, "*.json"))):
        r = json.load(open(path))
        if "error" in r:
            continue
        arch, shape_name = r["arch"], r["shape"]
        multi = r["mesh"] == "2x16x16"
        chips = r["chips"]
        shape = SHAPES[shape_name]
        cfg = get_config(arch)
        if arch in ARCH_OVERRIDES:
            cfg = dataclasses.replace(cfg, **ARCH_OVERRIDES[arch])
        dp = (2 * 16 if multi else 16)
        if shape.step == "train":
            cfg = dataclasses.replace(cfg, loss_chunk=512)
        dev_b = max(shape.global_batch // dp, 1)
        slab = dev_b * cfg.n_heads * shape.seq_len * 4
        chunk = 512
        while chunk > 64 and slab * chunk > (1 << 30):
            chunk //= 2
        cfg = dataclasses.replace(cfg, attn_chunk=chunk)
        n = cfg.param_count()
        recipe = TRAIN_RECIPE.get(arch, {})
        if shape.step == "train":
            pdt = recipe.get("param_dtype", jnp.float32)
            bits = recipe.get("state_bits", 32)
            pbytes = n * jnp.dtype(pdt).itemsize
            obytes = n * 2 * {32: 4, 16: 2, 8: 1}[bits]
            shards = chips
        else:
            pbytes, obytes, shards = n * 2, 0, 16
        roof = Roofline(
            arch=arch, shape=shape_name, mesh=r["mesh"], chips=chips,
            flops=r["roofline"]["hlo_flops_per_chip"],
            bytes_accessed=analytic_hbm_bytes(
                cfg, shape, chips, pbytes, obytes, param_shards=shards),
            coll_bytes=r["roofline"]["coll_bytes_per_chip"],
            coll_breakdown=r["roofline"].get("coll_breakdown", {}),
            model_flops=model_flops_estimate(cfg, shape),
            device_kind=V5E)
        r["roofline"] = roof.row()
        with open(path, "w") as f:
            json.dump(r, f, indent=1, default=str)
    print("rederived", len(glob.glob(os.path.join(OUT_DIR, "*.json"))))


if __name__ == "__main__":
    main()
