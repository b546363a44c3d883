"""Multi-device integration via subprocess (forced host devices): actually
EXECUTES a sharded train step (FSDP+TP+SP) and a sharded decode step on a
data x model mesh — the same code paths the 512-device dry-run lowers.

Default is a smoke-size run (2x2 mesh, one train step) so tier-1 stays
fast on small hosts; set ``REPRO_MULTIDEVICE_FULL=1`` for the original
4x2/8-device two-step version. The subprocesses pin ``JAX_PLATFORMS=cpu``
(forced host devices live on the CPU backend anyway): letting jax probe
for accelerator plugins cost ~8 min of backend-discovery timeouts *per
subprocess* on this image — that, not the compute, was the historical
">9 min on a 2-core host"."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

FULL = os.environ.get("REPRO_MULTIDEVICE_FULL") == "1"
N_DEV, N_STEPS = (8, 2) if FULL else (4, 1)


def run_sub(code: str, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-3000:])
    return out.stdout


HEADER = (
    "import os;"
    f"os.environ['XLA_FLAGS']="
    f"'--xla_force_host_platform_device_count={N_DEV}';"
    "import jax, jax.numpy as jnp, numpy as np, dataclasses;"
    "from repro.configs import get_config;"
    "from repro.models import lm_spec, init_params;"
    "from repro.optim import adamw;"
    "from repro.distributed import param_shardings, batch_shardings;"
    "from repro.distributed.sharding import set_activation_mesh;"
    "from repro.launch.steps import make_train_step;"
    "from repro.launch.mesh import make_host_mesh;"
    "mesh = make_host_mesh(model_axis=2);"
)


def test_sharded_train_step_executes():
    code = HEADER + (
        "cfg = dataclasses.replace(get_config('qwen2-0.5b', smoke=True),"
        " d_model=64, loss_chunk=16, attn_chunk=16);"
        "specs = lm_spec(cfg);"
        "set_activation_mesh(mesh);\n"
        "with mesh:\n"
        "  p_shard = param_shardings(specs, mesh, 'train');\n"
        "  params = jax.jit(lambda k: init_params(lm_spec(cfg), k),"
        " out_shardings=p_shard)(jax.random.PRNGKey(0));\n"
        "  opt = adamw.init(params);\n"
        "  batch = {'tokens': jnp.zeros((8, 64), jnp.int32),"
        " 'labels': jnp.ones((8, 64), jnp.int32)};\n"
        "  step = jax.jit(make_train_step(cfg, adamw.AdamWConfig()));\n"
        f"  for _ in range({N_STEPS}):\n"
        "    params, opt, m = step(params, opt, batch);\n"
        "  assert np.isfinite(float(m['loss'])), m;\n"
        "  print('ok', float(m['loss']))\n"
    )
    out = run_sub(code)
    assert "ok" in out


def test_sharded_decode_executes():
    code = HEADER + (
        "from repro.models import prefill, decode_step;"
        "from repro.models.transformer import lm_init_cache;"
        "cfg = get_config('gemma3-12b', smoke=True);"
        "params = init_params(lm_spec(cfg), jax.random.PRNGKey(0));"
        "set_activation_mesh(mesh);\n"
        "with mesh:\n"
        "  toks = jnp.zeros((8, 24), jnp.int32);\n"
        "  _, caches = prefill(params, cfg, tokens=toks, max_len=32);\n"
        "  lg, caches = decode_step(params, cfg,"
        " tokens=jnp.ones((8, 1), jnp.int32), caches=caches,"
        " pos=jnp.asarray(24, jnp.int32));\n"
        "  assert np.isfinite(np.asarray(lg, np.float32)).all();\n"
        "  print('ok')\n"
    )
    out = run_sub(code)
    assert "ok" in out
