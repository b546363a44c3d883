"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas kernel bodies on CPU; the grouped
scatter kernel defaults to the compiled TPU kernel, so these tests ask
for the interpreter themselves)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.grouped_scatter import (segment_sums, segment_sums_ref,
                                           grouped_scatter_apply,
                                           grouped_apply_ref)
from repro.kernels.flash_attention import flash_attention, attention_ref

RNG = np.random.default_rng(42)


class TestGroupedScatter:
    @pytest.mark.parametrize("n,d,g", [(64, 8, 4), (700, 130, 37),
                                       (1024, 256, 1), (33, 7, 33),
                                       (512, 64, 100)])
    @pytest.mark.parametrize("dtype", [np.float32, np.float16])
    def test_segment_sums_sweep(self, n, d, g, dtype):
        seg = jnp.asarray(np.sort(RNG.integers(0, g, n)).astype(np.int32))
        upd = jnp.asarray(RNG.normal(size=(n, d)).astype(dtype))
        got = segment_sums(seg, upd, g, interpret=True)
        want = segment_sums_ref(seg, upd, g)
        # long f32 reductions differ by accumulation order (blocked vs
        # sequential); tolerance per the long_reduction guidance
        tol = 2e-4 if dtype == np.float32 else 2e-2
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

    def test_unsorted_ids_also_work(self):
        seg = jnp.asarray(RNG.integers(0, 9, 200).astype(np.int32))
        upd = jnp.asarray(RNG.normal(size=(200, 16)).astype(np.float32))
        np.testing.assert_allclose(segment_sums(seg, upd, 9, interpret=True),
                                   segment_sums_ref(seg, upd, 9),
                                   rtol=1e-5, atol=1e-5)

    def test_negative_ids_dropped(self):
        seg = jnp.asarray(np.array([-1, 0, 0, 2, -1], np.int32))
        upd = jnp.ones((5, 4), jnp.float32)
        got = segment_sums(seg, upd, 3, interpret=True)
        np.testing.assert_allclose(np.asarray(got)[:, 0], [2, 0, 1])

    @pytest.mark.parametrize("hotness", [0, 200, 1800])
    def test_end_to_end_hot_apply(self, hotness):
        V, N, D = 300, 2048, 32
        ids = RNG.integers(0, V, N).astype(np.int32)
        if hotness:
            ids[:hotness] = 5
        ids = jnp.asarray(ids)
        upd = jnp.asarray(RNG.normal(size=(N, D)).astype(np.float32))
        table = jnp.asarray(RNG.normal(size=(V, D)).astype(np.float32))
        got = grouped_scatter_apply(table, ids, upd, threshold=32,
                                    interpret=True)
        want = grouped_apply_ref(table, ids, upd)
        # tolerance sized for f32 accumulation-order drift at 1800 adds/key
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("shape", [
        (2, 64, 64, 4, 2, 32),      # GQA
        (1, 128, 128, 8, 8, 64),    # MHA
        (2, 96, 96, 6, 1, 16),      # MQA
        (1, 256, 256, 2, 2, 128),   # long-ish
    ])
    @pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
    def test_sweep_vs_ref(self, shape, dtype):
        B, Sq, Sk, H, K, D = shape
        dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
        q = jnp.asarray(RNG.normal(size=(B, Sq, H, D)), dt)
        k = jnp.asarray(RNG.normal(size=(B, Sk, K, D)), dt)
        v = jnp.asarray(RNG.normal(size=(B, Sk, K, D)), dt)
        got = flash_attention(q, k, v, causal=True)
        want = attention_ref(q, k, v, causal=True)
        tol = 2e-6 if dt == jnp.float32 else 2e-2
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)

    def test_noncausal(self):
        q = jnp.asarray(RNG.normal(size=(1, 64, 2, 32)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(1, 64, 2, 32)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(1, 64, 2, 32)), jnp.float32)
        np.testing.assert_allclose(
            flash_attention(q, k, v, causal=False),
            attention_ref(q, k, v, causal=False), rtol=2e-6, atol=2e-6)

    def test_matches_model_attention_path(self):
        """The kernel slot in gqa_attend agrees with the jnp path."""
        import dataclasses
        import jax
        from repro.configs import get_config
        from repro.models.attention import gqa_spec, gqa_attend
        from repro.models.common import init_params
        cfg = get_config("deepseek-coder-33b", smoke=True)
        p = init_params(gqa_spec(cfg), __import__("jax").random.PRNGKey(0))
        x = jnp.asarray(RNG.normal(size=(2, 64, cfg.d_model)), jnp.float32)
        a, _ = gqa_attend(p, x, cfg, "global", "train", use_kernel=False)
        b, _ = gqa_attend(p, x, cfg, "global", "train", use_kernel=True)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
