"""End-to-end training driver: train a ~100M-param LM for a few hundred
steps with checkpoint/restart.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 200
On this 1-core CPU container a 200-step run takes hours; for a quick
functional pass use:
      PYTHONPATH=src python examples/train_lm.py --steps 6 --batch 2 --seq 64
(the same driver runs the full setting on a real pod).
"""

import argparse
import dataclasses

import jax

from repro.configs.base import ModelConfig
from repro.data import synthetic
from repro.models import api
from repro.train import loop, optim
from repro.launch.mesh import make_mesh
from repro.launch.cache import use_compile_cache

# ~100M params: 12 layers, d=768 (tinyllama family); param_count() = 129M
CFG_100M = ModelConfig(
    name="demo-100m", n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
    d_ff=3072, vocab_size=16384, pattern=("attn",), rope_theta=1e4,
    norm="rms", gated_mlp=True, act="silu")


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/nero_train_lm")
    args = ap.parse_args()

    cfg = CFG_100M
    model = api.build(cfg)
    n = cfg.param_count()
    print(f"training {cfg.name}: {n / 1e6:.1f}M params, "
          f"{args.steps} steps @ batch {args.batch} seq {args.seq}")
    mesh = make_mesh((1, 1), ("data", "model"))
    data = synthetic.iterator(cfg, args.batch, args.seq)
    opt_cfg = optim.OptConfig(lr=1e-3, warmup_steps=20,
                              total_steps=args.steps)
    params, _, hist = loop.fit(model, mesh, data, steps=args.steps,
                               opt_cfg=opt_cfg, ckpt_dir=args.ckpt_dir,
                               ckpt_every=100, log_every=20)
    if not hist:
        print(f"checkpoint in {args.ckpt_dir} is already at step "
              f">= {args.steps}; nothing to do (rm -r it to retrain)")
        print("train_lm OK")
        return
    print(f"loss: {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    if len(hist) > 20:
        assert hist[-1]["loss"] < hist[0]["loss"]
    print("train_lm OK")


if __name__ == "__main__":
    main()
