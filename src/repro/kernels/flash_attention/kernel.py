"""Pallas TPU flash attention (causal, GQA via index_map).

Classic three-scratch online-softmax formulation:
  grid = (B, H, Sq/BQ, Sk/BK), the KV axis innermost and "arbitrary";
  scratch (VMEM): running max m (BQ,1), running sum l (BQ,1), acc (BQ,D).
  Fully-masked KV blocks are skipped with pl.when (causal early-exit).
GQA never materializes repeated KV: the K/V BlockSpec index_map sends
query head h to kv head h // (H // K).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEF_BQ = 256
DEF_BK = 256
NEG_INF = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, bq: int, bk: int,
                  kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q0 = qi * bq
    k0 = ki * bk
    live = (k0 <= q0 + bq - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)
        if causal:
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == kv_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "bq", "bk",
                                             "interpret"))
def flash_attention_bhsd(q, k, v, causal=True, scale=None,
                         bq: int = DEF_BQ, bk: int = DEF_BK,
                         interpret: bool = True):
    """q: (B, H, Sq, D); k/v: (B, K, Sk, D). Returns (B, H, Sq, D) f32."""
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    assert H % K == 0
    group = H // K
    scale = scale if scale is not None else D ** -0.5
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    kv_blocks = Sk // bk
    grid = (B, H, Sq // bq, kv_blocks)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, kv_blocks=kv_blocks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
