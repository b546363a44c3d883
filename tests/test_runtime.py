"""Fault tolerance, stragglers, data pipeline, optimizer, serving."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import (HeartbeatMonitor, reshard_plan,
                               StragglerDetector, rebalance, plan_recovery)
from repro.launch.mesh import elastic_mesh_shape
from repro.data import DataConfig, init_state, make_batch
from repro.configs import get_config
from repro.optim import adamw, quantized_psum
from repro.checkpoint import Journal


class TestFault:
    def test_heartbeat_detects_failure(self):
        hb = HeartbeatMonitor(timeout_s=10)
        hb.beat(0, now=0.0)
        hb.beat(1, now=0.0)
        hb.beat(0, now=20.0)
        assert hb.failed(now=21.0) == [1]
        assert hb.alive(now=21.0) == [0]

    def test_reshard_plan_covers_all_shards(self):
        plan = reshard_plan([0, 1, 2, 3], [0, 2, 3], 16)
        got = sorted(s for v in plan.values() for s in v)
        assert got == list(range(16))
        sizes = [len(v) for v in plan.values()]
        assert max(sizes) - min(sizes) <= 1

    def test_elastic_mesh_preserves_model_axis(self):
        shape, axes = elastic_mesh_shape(240, model_axis=16)
        assert shape == (15, 16) and axes == ("data", "model")
        shape, _ = elastic_mesh_shape(100, model_axis=16)
        assert 100 % shape[1] == 0

    def test_plan_recovery(self, tmp_path):
        j = Journal(str(tmp_path / "j.jsonl"))
        j.commit(7, j.assign(7))
        hb = HeartbeatMonitor(timeout_s=5)
        for h in range(4):
            hb.beat(h, now=0.0)
        hb.beat(3, now=100.0)      # only 3 survives... others at t=0
        dec = plan_recovery(hb, j, devices_per_host=8, model_axis=4,
                            now=101.0)
        assert dec.restore_step == 7
        assert dec.mesh_shape[1] == 4


class TestStraggler:
    def test_detect_and_eject(self):
        det = StragglerDetector(alpha=1.0, threshold=1.4, eject_after=2)
        for _ in range(3):
            for h in range(4):
                det.observe(h, 1.0 if h else 2.0)   # host 0 slow
            s = det.stragglers()
        assert s == [0]
        assert det.ejections() == [0]

    def test_rebalance_moves_work(self):
        plan = {0: [0, 1, 2, 3], 1: [4, 5], 2: [6, 7]}
        new = rebalance(plan, straggler=0, fraction=0.5)
        assert len(new[0]) == 2
        assert sorted(s for v in new.values() for s in v) == list(range(8))


class TestData:
    def test_deterministic(self):
        cfg = get_config("qwen2-0.5b", smoke=True)
        dc = DataConfig(seed=3)
        b1, s1 = make_batch(dc, cfg, 4, 32, init_state())
        b2, _ = make_batch(dc, cfg, 4, 32, init_state())
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        b3, _ = make_batch(dc, cfg, 4, 32, s1)
        assert not np.array_equal(b1["tokens"], b3["tokens"])

    def test_hosts_get_different_data(self):
        cfg = get_config("qwen2-0.5b", smoke=True)
        b1, _ = make_batch(DataConfig(host_id=0), cfg, 4, 32, init_state())
        b2, _ = make_batch(DataConfig(host_id=1), cfg, 4, 32, init_state())
        assert not np.array_equal(b1["tokens"], b2["tokens"])

    def test_zipf_skew_creates_hotspots(self):
        cfg = get_config("qwen2-0.5b", smoke=True)
        b, _ = make_batch(DataConfig(zipf_s=1.2), cfg, 8, 128, init_state())
        toks = np.asarray(b["tokens"]).reshape(-1)
        _, counts = np.unique(toks, return_counts=True)
        assert counts.max() > 32      # the paper's hot threshold is hit

    def test_labels_shift(self):
        cfg = get_config("qwen2-0.5b", smoke=True)
        b, _ = make_batch(DataConfig(), cfg, 2, 16, init_state())
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


class TestOptim:
    def _quad_losses(self, bits, steps=60):
        cfg = adamw.AdamWConfig(peak_lr=0.1, warmup_steps=1,
                                decay_steps=1000, weight_decay=0.0,
                                state_bits=bits)
        params = {"w": jnp.ones((64,)) * 3.0}
        opt = adamw.init(params, bits)
        for _ in range(steps):
            grads = {"w": 2 * params["w"]}
            params, opt, _ = adamw.apply(cfg, grads, opt, params)
        return float(jnp.abs(params["w"]).max())

    @pytest.mark.parametrize("bits", [32, 16, 8])
    def test_adamw_converges_all_state_widths(self, bits):
        assert self._quad_losses(bits) < 0.5

    def test_quantized_psum_single_device(self):
        # axis size 1: quantization error only. check_vma=False because
        # the manual ring's replication cannot be statically inferred.
        mesh = jax.make_mesh((1,), ("d",))
        from jax.sharding import PartitionSpec as P
        x = jnp.linspace(-1, 1, 4096)
        f = jax.shard_map(lambda v: quantized_psum(v, "d")[0], mesh=mesh,
                          in_specs=P(), out_specs=P(), check_vma=False)
        np.testing.assert_allclose(f(x), x, atol=2e-2)

    def test_quantized_psum_multidevice_subprocess(self):
        """8 forced host devices: quantized ring-all-reduce ~= exact psum."""
        import subprocess, sys, os
        code = (
            "import os;"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=8';"
            "import jax, jax.numpy as jnp, numpy as np;"
            "from jax.sharding import PartitionSpec as P;"
            "from repro.optim import quantized_psum;"
            "mesh = jax.make_mesh((8,), ('d',));"
            "x = jnp.arange(8 * 512, dtype=jnp.float32)"
            ".reshape(8, 512) / 1000.0;"
            "f = jax.shard_map(lambda v: quantized_psum(v[0], 'd')[0][None],"
            " mesh=mesh, in_specs=P('d'), out_specs=P('d'),"
            " check_vma=False);"
            "got = np.asarray(f(x));"
            "want = np.asarray(x.sum(0));"
            "err = np.abs(got - want).max() / max(np.abs(want).max(), 1);"
            "assert err < 0.05, err; print('ok', err)"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(
            os.path.dirname(__file__), "..", "src")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "ok" in out.stdout


class TestServe:
    def test_group_server_serves_all_in_order(self):
        from repro.launch.serve import serve_demo
        srv = serve_demo(n_requests=9, batch_slots=4)
        assert all(r is None for r in srv.active)
        assert srv.members_served > 0
        # dynamic batch: fused steps < total tokens (grouping worked)
        assert srv.steps_fired < srv.members_served
