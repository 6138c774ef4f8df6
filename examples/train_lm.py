"""Train a ~100M-parameter LM for a few hundred steps on synthetic data.

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--arch llama3.2-1b]

Uses the training substrate end to end: config -> init -> AdamW(+schedule)
-> jit'd train step -> checkpoint.  The ~100M variant is the assigned arch's
family scaled to d_model=768 / 12 layers (not the 2-layer smoke config).
"""

import argparse
import dataclasses
import sys

sys.path.insert(0, "src")

import jax

from repro.config import get_config
from repro.models import init_params
from repro.training import AdamWConfig, TrainConfig, lm_batches, save_checkpoint, train_loop


def hundred_m_config(arch: str):
    cfg = get_config(arch)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-100m",
        n_layers=12,
        d_model=768,
        n_heads=12 if cfg.n_heads else 0,
        n_kv_heads=4 if cfg.n_kv_heads else 0,
        head_dim=64,
        d_ff=2048 if cfg.d_ff else 0,
        vocab_size=32000,
    )


def main() -> None:
    from repro.kernels.dispatch import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--out", default="/tmp/repro_ckpt/lm")
    args = ap.parse_args()

    cfg = hundred_m_config(args.arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"{cfg.name}: {n_params/1e6:.1f}M params "
          f"({cfg.n_layers}L d={cfg.d_model} vocab={cfg.vocab_size}) "
          f"schedule={cfg.lr_schedule}")

    tcfg = TrainConfig(
        adamw=AdamWConfig(lr=args.lr),
        warmup_steps=max(args.steps // 20, 5),
        total_steps=args.steps,
    )
    params, opt, hist = train_loop(
        params, cfg, tcfg,
        lm_batches(cfg, batch=args.batch, seq=args.seq, seed=0),
        steps=args.steps, log_every=max(args.steps // 15, 1),
    )
    print(f"\nloss: {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    save_checkpoint(args.out, params, metadata={"arch": cfg.name, "steps": args.steps})
    print(f"checkpoint written to {args.out}.npz")


if __name__ == "__main__":
    main()
