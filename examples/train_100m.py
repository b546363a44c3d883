"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps
with checkpointing, resume, hotspot-grouped embedding updates, and
straggler/heartbeat monitoring.

    PYTHONPATH=src python examples/train_100m.py --steps 300
"""
import argparse
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses

from repro.configs.base import ModelConfig
import repro.configs as configs
from repro.launch import train as train_mod
from repro.compile_cache import enable_compile_cache

# ~100M params: 640 width, 8 layers, GQA 8/4
CONFIG_100M = ModelConfig(
    name="repro-100m",
    family="dense",
    layout=(((("global", "dense"),), 8),),
    d_model=640,
    n_heads=8,
    n_kv_heads=4,
    d_ff=2560,
    vocab=32_000,
    head_dim=80,
    vocab_pad_to=128,
    remat=False,
    source="examples/train_100m.py",
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_100m")
    args = ap.parse_args()

    print(f"params ~= {CONFIG_100M.param_count() / 1e6:.0f}M")
    # register as a transient arch so the driver can pick it up
    mod = dataclasses.make_dataclass("M", [])()
    mod.CONFIG = CONFIG_100M
    mod.SMOKE = CONFIG_100M
    configs._MODULES["repro-100m"] = mod

    losses = train_mod.train(
        "repro-100m", smoke=False, steps=args.steps, batch=args.batch,
        seq=args.seq, ckpt_dir=args.ckpt_dir, ckpt_every=100)
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({len(losses)} steps)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
