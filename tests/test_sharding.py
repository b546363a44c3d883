"""Sharding resolver tests (AbstractMesh — no devices needed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, SHAPES, input_specs
from repro.models import lm_spec
from repro.models.transformer import lm_cache_shapes
from repro.distributed.sharding import (RULES, resolve_spec, param_pspecs,
                                        ResolveReport, _cache_leaf_pspec,
                                        cache_shardings)

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestResolver:
    def test_fsdp_tp_two_axes(self):
        s = resolve_spec((7168, 19200), ("embed", "mlp"), MESH,
                         RULES["train"])
        assert s == P("data", "model")

    def test_vocab_two_axis_when_divisible(self):
        s = resolve_spec((32256, 7168), ("vocab", "embed"), MESH,
                         RULES["train"])
        assert s[0] == ("data", "model")

    def test_divisibility_fallback(self):
        rep = ResolveReport()
        # 151936 % 256 != 0 -> falls to single-axis sharding
        s = resolve_spec((151936, 896), ("vocab", "embed"), MESH,
                         RULES["train"], rep)
        assert s[0] == "model"

    def test_no_axis_reuse_within_tensor(self):
        s = resolve_spec((128, 7168, 4864), ("experts", "embed", "mlp"),
                         MESH, RULES["train"])
        used = [a for a in jax.tree.leaves(tuple(s)) if a]
        assert len(set(used)) == len(used)

    def test_replicate_when_nothing_fits(self):
        s = resolve_spec((7,), ("heads",), MESH, RULES["train"])
        assert s == P(None)

    def test_serve_rules_keep_weights_off_data_axis(self):
        # dense mlp: model only; embed: replicated (no per-step gathers)
        s = resolve_spec((896, 4864), ("embed", "mlp"), MESH,
                         RULES["serve"])
        assert s == P(None, "model")

    def test_serve_expert_ff_spills_to_data(self):
        # arctic-480b: experts on model, ff on data => weights fit a pod
        s = resolve_spec((128, 7168, 4864), ("experts", "embed", "mlp"),
                         MESH, RULES["serve"])
        assert s[0] == "model" and s[2] == "data"

    @pytest.mark.parametrize("arch", ["deepseek-coder-33b", "arctic-480b",
                                      "mamba2-1.3b"])
    @pytest.mark.parametrize("mesh", [MESH, MESH3])
    def test_full_trees_resolve(self, arch, mesh):
        cfg = get_config(arch)
        tree = param_pspecs(lm_spec(cfg), mesh, "train")
        for ps in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, P)):
            assert isinstance(ps, P)


class TestCacheShardings:
    def test_kv_heads_preferred_when_divisible(self):
        # 16 kv heads % 16 == 0 -> heads axis
        s = _cache_leaf_pspec(MESH, "k", (27, 128, 32768, 16, 128), True)
        assert s[3] == "model" and s[1] in ("data", ("data",))

    def test_seq_fallback_when_heads_indivisible(self):
        s = _cache_leaf_pspec(MESH, "k", (62, 128, 32768, 8, 128), True)
        assert s[2] == "model"        # 8 kv heads % 16 != 0 -> shard seq

    def test_head_dim_never_sharded(self):
        s = _cache_leaf_pspec(MESH, "k", (2, 128, 100, 3, 128), True)
        assert s[4] is None

    def test_batch_one_replicates(self):
        s = _cache_leaf_pspec(MESH, "k", (48, 1, 524288, 8, 240), True)
        assert s[1] is None and s[2] == "model"

    @pytest.mark.parametrize("arch", ["gemma3-12b", "mamba2-1.3b",
                                      "deepseek-v2-lite-16b"])
    def test_full_cache_tree(self, arch):
        cfg = get_config(arch)
        caches = lm_cache_shapes(cfg, 128, 32768)
        tree = cache_shardings(caches, MESH)
        assert jax.tree.leaves(tree)
