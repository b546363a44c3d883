"""Kernel-level schedule comparison (CPU wall-clock is a schedule proxy,
not a TPU claim): hotspot-grouped scatter-apply vs XLA's serialized
duplicate-index scatter, under Zipf duplication."""
import time

import numpy as np
import jax
import jax.numpy as jnp

from .common import emit
from repro.core.group_apply import group_apply, scatter_serial
from repro.core.lock.workload import zipf_cdf


def _time(f, *args, reps=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else \
        jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run(quick=True):
    rows = []
    rng = np.random.default_rng(0)
    V, D = 50_000, 512
    N = 32_768 if quick else 262_144
    table = jnp.zeros((V, D), jnp.float32)
    upd = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    cdf = zipf_cdf(V, 1.2)
    for skew, name in [(None, "uniform"), (cdf, "zipf1.2")]:
        if skew is None:
            ids = rng.integers(0, V, N)
        else:
            ids = np.searchsorted(skew, rng.random(N))
        ids = jnp.asarray(ids.astype(np.int32))
        f_serial = jax.jit(scatter_serial)
        f_group = jax.jit(group_apply)
        t_ser = _time(f_serial, table, ids, upd)
        t_grp = _time(f_group, table, ids, upd)
        dup = N / len(np.unique(np.asarray(ids)))
        rows.append(f"kernel_scatter_serial_{name},{t_ser:.0f},dup={dup:.1f}")
        rows.append(f"kernel_scatter_grouped_{name},{t_grp:.0f},"
                    f"speedup={t_ser / t_grp:.2f}")
    return emit(rows)


if __name__ == "__main__":
    run()
