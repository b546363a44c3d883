"""Gradient compression: int8 ring all-reduce with error feedback (a
distributed-optimization trick for the slow multi-pod axis).

Runs inside ``shard_map`` over a data-parallel mesh axis. Each step:
  1. add the error-feedback residual to the local gradient,
  2. quantize to int8 with per-block f32 scales (4x less wire than f32,
     2x less than bf16),
  3. ring all-reduce via ``lax.ppermute`` — each hop moves int8 + scales,
  4. keep the quantization error as next step's residual (so the bias is
     corrected over steps; standard EF-SGD argument).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 2048  # quantization block (per-block scale)


def _blocked(x: jnp.ndarray):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % BLOCK
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, BLOCK), pad


def quantize(x: jnp.ndarray):
    """x: (..., B). Returns int8 values + f32 per-row scales."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def quantized_psum(x: jnp.ndarray, axis_name: str, residual=None):
    """Quantized ring all-reduce over `axis_name` (call inside shard_map).

    Returns (sum over the axis, new error-feedback residual). The sum is
    of *quantized* contributions; each device's quantization error stays
    local in `residual` and is re-injected next call.
    """
    n = lax.axis_size(axis_name)
    xf = lax.pcast(x.astype(jnp.float32), (axis_name,), to="varying")
    if residual is not None:
        xf = xf + lax.pcast(residual, (axis_name,), to="varying")
    blocks, pad = _blocked(xf)
    q, s = quantize(blocks)
    err = (blocks - dequantize(q, s)).reshape(-1)
    err = (err[:-pad] if pad else err).reshape(x.shape)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(_, carry):
        acc, q, s = carry
        q = lax.ppermute(q, axis_name, perm)
        s = lax.ppermute(s, axis_name, perm)
        return acc + dequantize(q, s), q, s

    acc = dequantize(q, s)
    acc, _, _ = lax.fori_loop(0, n - 1, hop, (acc, q, s))
    out = acc.reshape(-1)
    out = (out[:-pad] if pad else out).reshape(x.shape)
    return out, err
