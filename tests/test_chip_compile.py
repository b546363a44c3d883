"""Ahead-of-time compiles of the main path for a described TPU v5e chip.

The TPU compiler is installed with JAX, so the programs the chip runs are
compiled here for a chip that is described and not attached: the engine's
single-run loop at SysBench table scale (1,000,000 rows, 1024 threads),
the packed segment loop at the YCSB-style zipf shape, the served segment
loop (whose end-of-segment snapshot must hold no scatter), the serving
histogram fold, and the grouped-scatter Pallas kernel in compiled mode.
A refusal the chip's compiler would raise fails here. Nothing runs.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file. The persistent compilation cache is off
around these compiles (a described device cannot read its entries back).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lock import (CostModel, EngineConfig, WorkloadSpec,
                             init_state_dyn, protocol_params, split_config)
from repro.core.lock import engine as E

ROWS = 1_000_000
HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from repro.compile_cache import cache_off
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs on disk
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        with cache_off():
            yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree, lanes=None):
    """Shape-only view of ``tree`` placed on ``sharding`` (optionally with
    a leading lane axis)."""
    lead = () if lanes is None else (lanes,)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(lead + x.shape, x.dtype,
                                       sharding=sharding), tree)


def _engine_args(kind, threads, txn_len, **wl):
    cfg = EngineConfig(
        protocol=protocol_params("group"), costs=CostModel(),
        workload=WorkloadSpec(kind=kind, n_rows=ROWS, txn_len=txn_len,
                              **wl),
        n_threads=threads)
    stat, dp = split_config(cfg)
    s0 = jax.eval_shape(lambda d: init_state_dyn(stat, d), dp)
    return stat, dp, s0


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def test_run_dyn_hotspot_table_scale(one_chip):
    stat, dp, s0 = _engine_args("hotspot_update", 1024, 1)
    compiled = E._run_dyn.lower(stat, _on(one_chip, dp),
                                _on(one_chip, s0)).compile()
    _fits(compiled)


def test_run_seg_batch_zipf_four_lanes(one_chip):
    stat, dp, s0 = _engine_args("zipf", 256, 4, zipf_s=0.99)
    untils = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    compiled = E._run_seg_batch.lower(
        stat, _on(one_chip, dp, lanes=4), _on(one_chip, s0, lanes=4),
        untils).compile()
    _fits(compiled)


# a scatter whose op_name sits directly in the snapshot's scope (not in
# its stage_derive sub-scope, which scatters only T*L updates)
_SNAPSHOT_SCATTER = re.compile(r'op_name="[^"]*/seg_snapshot/[^"/]*scatter')


def test_run_seg_dyn_snapshot_has_no_scatter(one_chip):
    # the served cell's shape: 1M rows, 256 threads, four updates each
    # (the protocol is traced data, so group compiles mysql's program)
    stat, dp, s0 = _engine_args("zipf", 256, 4, zipf_s=0.99)
    until = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = E._run_seg_dyn.lower(stat, _on(one_chip, dp),
                                    _on(one_chip, s0), until).compile()
    hlo = compiled.as_text()
    assert "/seg_snapshot/" in hlo
    assert not _SNAPSHOT_SCATTER.findall(hlo)
    _fits(compiled)


def test_serving_hist_add(one_chip):
    from repro.serving.runner import _hist_add
    hist = jax.ShapeDtypeStruct((E.N_HIST,), jnp.int32, sharding=one_chip)
    ticks = jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((1024,), jnp.bool_, sharding=one_chip)
    _fits(_hist_add.lower(hist, ticks, valid).compile())


def test_segment_sums_compiles_to_tpu_kernel(one_chip):
    from repro.kernels.grouped_scatter import segment_sums
    seg = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one_chip)
    upd = jax.ShapeDtypeStruct((4096, 256), jnp.float32, sharding=one_chip)
    compiled = segment_sums.lower(seg, upd, num_groups=256).compile()
    assert "tpu_custom_call" in compiled.as_text()
