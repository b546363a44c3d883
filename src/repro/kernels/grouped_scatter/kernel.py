"""Pallas TPU kernel: blocked segment reduction as a one-hot MXU matmul.

Hardware adaptation (DESIGN.md §2.3): the paper's group locking turns many
conflicting row updates into one lock + a serial in-group apply. On TPU,
"serial in-group apply" maps to a *reduction*; the highest-throughput
reduction unit is the MXU, so conflict groups are folded with a blocked
one-hot matmul:

    sums[g, :] = sum_n [seg_id[n] == g] * updates[n, :]

Grid: (groups/BG, D/BD, N/BN) — the N axis is innermost ("arbitrary"
semantics) and accumulates into the (BG, BD) output block in VMEM; the
first N-step zero-initializes (classic revisited-output pattern). The
one-hot block never exists in HBM — it is synthesized in VMEM from the
(BN,) id block via an iota compare, which is exactly the VMEM-locality
rethink the kernel taxonomy prescribes for scatter/gather on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEF_BG = 128      # group rows per block   (MXU lane dim)
DEF_BD = 256      # feature columns per block
DEF_BN = 512      # update rows per block  (contraction dim)


def _seg_matmul_kernel(seg_ref, upd_ref, out_ref):
    g = pl.program_id(0)
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[0, :]                           # (BN,) i32 group ids
    bg = out_ref.shape[0]
    g0 = g * bg
    # synthesize the one-hot block in VMEM: (BG, BN)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bg, seg.shape[0]), 0) + g0
    onehot = (rows == seg[None, :]).astype(jnp.float32)
    out_ref[...] += jax.lax.dot(
        onehot, upd_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("num_groups", "bg", "bd", "bn",
                                    "interpret"))
def segment_sums(seg_ids: jnp.ndarray, updates: jnp.ndarray,
                 num_groups: int, bg: int = DEF_BG, bd: int = DEF_BD,
                 bn: int = DEF_BN, interpret: bool = False) -> jnp.ndarray:
    """Blocked one-hot segment sum. seg_ids: (N,) sorted (any order works —
    sortedness only improves one-hot block sparsity); updates: (N, D).

    Returns (num_groups, D) f32. Rows with seg_id outside [0, num_groups)
    are dropped. Compiled for the TPU unless ``interpret=True`` (the CPU
    tests' Pallas interpreter).
    """
    N, D = updates.shape
    bg = min(bg, max(8, num_groups))
    bd = min(bd, D)
    bn = min(bn, N)
    G = pl.cdiv(num_groups, bg) * bg
    Np = pl.cdiv(N, bn) * bn
    Dp = pl.cdiv(D, bd) * bd
    if Np != N:
        seg_ids = jnp.pad(seg_ids, (0, Np - N), constant_values=-1)
        updates = jnp.pad(updates, ((0, Np - N), (0, 0)))
    if Dp != D:
        updates = jnp.pad(updates, ((0, 0), (0, Dp - D)))

    grid = (G // bg, Dp // bd, Np // bn)
    out = pl.pallas_call(
        _seg_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn), lambda g, d, n: (0, n)),
            pl.BlockSpec((bn, bd), lambda g, d, n: (n, d)),
        ],
        out_specs=pl.BlockSpec((bg, bd), lambda g, d, n: (g, d)),
        out_shape=jax.ShapeDtypeStruct((G, Dp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(seg_ids[None, :], updates)
    return out[:num_groups, :D]
