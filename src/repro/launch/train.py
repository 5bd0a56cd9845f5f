"""Training launcher.

CPU-runnable with ``--preset tiny`` (reduced width, real arch family); the
full configs are exercised by ``dryrun.py``.  Supports checkpoint/restart
(``--resume``), fault injection (``--fail-at``), and elastic resharding
(resume the same checkpoint with a different ``--mesh``).

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b --preset tiny \
        --steps 50 --mesh 1x1 --ckpt /tmp/run1
"""

from __future__ import annotations

import argparse
import dataclasses
import logging

import jax

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim import AdamWConfig
from repro.runtime import Trainer, TrainerConfig


def tiny(cfg):
    kw = dict(
        n_layers=2, d_model=128, d_ff=256 if cfg.d_ff else 0, n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2), head_dim=32, vocab_size=1024,
        dtype="float32", cross_context=16 if cfg.cross_context else 0,
    )
    if cfg.moe:
        kw["moe"] = dataclasses.replace(cfg.moe, n_experts=4, top_k=2, d_ff_expert=64,
                                        first_dense_layers=min(cfg.moe.first_dense_layers, 1))
    if cfg.mla:
        kw["mla"] = dataclasses.replace(cfg.mla, kv_lora_rank=32, rope_head_dim=16,
                                        nope_head_dim=32, v_head_dim=32)
        kw["head_dim"] = 48
    if cfg.ssm:
        kw["ssm"] = dataclasses.replace(cfg.ssm, state_dim=16, head_dim=16, chunk=16)
    if cfg.encoder:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=2, context=16)
    if cfg.window:
        kw["window"] = 32
    return dataclasses.replace(cfg, **kw)


def small_100m(cfg):
    """~100M-parameter config for the end-to-end example run."""
    kw = dict(n_layers=8, d_model=512, d_ff=1536 if cfg.d_ff else 0, n_heads=8,
              n_kv_heads=min(cfg.n_kv_heads, 4), head_dim=64, vocab_size=32768,
              dtype="float32")
    if cfg.ssm:
        kw["ssm"] = dataclasses.replace(cfg.ssm, state_dim=64, head_dim=32, chunk=64)
    return dataclasses.replace(cfg, **kw)


PRESETS = {"tiny": tiny, "100m": small_100m, "full": lambda c: c}


def main() -> None:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--preset", choices=list(PRESETS), default="tiny")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x4")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()
    use_compile_cache()

    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_host_mesh(d, m)
    cfg = PRESETS[args.preset](get_config(args.arch))
    trainer = Trainer(
        cfg,
        mesh,
        TrainerConfig(
            steps=args.steps, batch=args.batch, seq_len=args.seq,
            checkpoint_dir=args.ckpt, fail_at_step=args.fail_at,
            log_every=max(args.steps // 10, 1),
            checkpoint_every=max(args.steps // 4, 1),
        ),
        AdamWConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                    total_steps=args.steps),
    )
    out = trainer.run(resume=args.resume)
    losses = out["history"]
    print(f"first loss {losses[0]['loss']:.4f} -> last loss {losses[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
