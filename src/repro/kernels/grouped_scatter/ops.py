"""Jitted wrapper: hotspot-grouped scatter-apply built on the Pallas
segment-matmul kernel.

Pipeline (paper §4.1-§4.2 on tensors):
  1. detect hot ids (in-batch conflict count > threshold),
  2. cold ids -> native scatter (2PL path),
  3. hot ids -> conflict groups: stable sort, group index per row
     (``hot_update_order`` is the sort order), Pallas segment reduction,
     one scatter per distinct hot row (the leader's single write).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.hotspot import batch_counts, DEFAULT_THRESHOLD
from .kernel import segment_sums


@functools.partial(jax.jit, static_argnames=("threshold", "max_hot",
                                             "interpret"))
def grouped_scatter_apply(table: jnp.ndarray, ids: jnp.ndarray,
                          updates: jnp.ndarray,
                          threshold: int = DEFAULT_THRESHOLD,
                          max_hot: int = 256,
                          interpret: bool = False) -> jnp.ndarray:
    """Apply (ids -> updates) into table rows, hot rows via the kernel.

    max_hot: static bound on distinct hot rows per batch (hot rows are by
    definition few — the paper's premise).
    """
    V, D = table.shape
    ids = ids.reshape(-1)
    updates = updates.reshape(-1, D)
    N = ids.shape[0]

    counts = batch_counts(ids, V)
    hot_row = counts > threshold                      # (V,) mask
    is_hot = hot_row[ids]                             # (N,)

    # ---- cold path: native scatter (2PL) ----
    sentinel = jnp.int32(V)
    cold_ids = jnp.where(is_hot, sentinel, ids)
    out = table.at[cold_ids].add(
        jnp.where(is_hot[:, None], 0, updates).astype(table.dtype),
        mode="drop")

    # ---- hot path: conflict groups -> Pallas segment reduce ----
    # enumerate distinct hot rows (static bound max_hot)
    hot_rows = jnp.nonzero(hot_row, size=max_hot, fill_value=V)[0]  # (H,)
    # group index of each update: position of its row in hot_rows
    gidx = jnp.searchsorted(hot_rows, ids).astype(jnp.int32)
    gvalid = is_hot & (hot_rows[jnp.clip(gidx, 0, max_hot - 1)] == ids)
    gidx = jnp.where(gvalid, gidx, -1)
    sums = segment_sums(gidx, jnp.where(gvalid[:, None], updates, 0),
                        num_groups=max_hot, interpret=interpret)
    # one write per group (leader lock/release once)
    return out.at[hot_rows].add(sums.astype(table.dtype), mode="drop")
