"""Quickstart: the paper's result in ~a minute, plus a tiny training run.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys
import os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.core.lock import simulate, extract, WorkloadSpec
from repro.configs import get_config
from repro.models import lm_spec, init_params
from repro.optim import adamw
from repro.data import DataConfig, init_state, make_batch
from repro.launch.steps import make_train_step
from repro.compile_cache import enable_compile_cache


def cc_demo():
    print("=== TXSQL group locking vs baselines "
          "(SysBench hotspot update, 256 threads) ===")
    w = WorkloadSpec(kind="hotspot_update", txn_len=1, n_rows=512)
    base = None
    for proto in ["mysql", "o1", "o2", "bamboo", "group"]:
        r = extract(proto, 256,
                    simulate(proto, w, n_threads=256, horizon=200_000))
        base = base or r.tps
        tag = {"group": "TXSQL (group locking)"}.get(proto, proto)
        print(f"  {tag:24s} {r.tps:>9.0f} TPS   "
              f"({r.tps / base:4.1f}x MySQL)")


def train_demo(steps=20):
    print("\n=== 20 training steps, qwen2-0.5b (smoke config) ===")
    cfg = get_config("qwen2-0.5b", smoke=True)
    params = init_params(lm_spec(cfg), jax.random.PRNGKey(0))
    opt_cfg = adamw.AdamWConfig(peak_lr=1e-3, warmup_steps=2,
                                decay_steps=steps)
    opt = adamw.init(params)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    ds, dc = init_state(), DataConfig()
    for i in range(steps):
        batch, ds = make_batch(dc, cfg, 8, 64, ds)
        params, opt, m = step(params, opt, batch)
        if i % 5 == 0 or i == steps - 1:
            print(f"  step {i:3d}  loss {float(m['loss']):.4f}")


if __name__ == "__main__":
    enable_compile_cache()
    cc_demo()
    train_demo()
