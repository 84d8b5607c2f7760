"""Training launcher: runs any assigned architecture on the local devices.

Full-size configs are for the production meshes (use dryrun.py to validate
those); local runs default to the reduced smoke config unless --full.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --steps 100 --batch 8 --seq 256 --ckpt /tmp/ckpt [--resume]
"""
from __future__ import annotations

import argparse

from .. import configs
from ..runtime import Trainer, TrainerConfig
from .compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=configs.list_archs())
    ap.add_argument("--full", action="store_true",
                    help="use the full-size config (pod-scale!)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress", default="none",
                    choices=["none", "bf16", "int8"])
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = (configs.get_config(args.arch) if args.full
           else configs.get_smoke_config(args.arch))
    tcfg = TrainerConfig(steps=args.steps, batch_size=args.batch,
                         seq_len=args.seq, checkpoint_dir=args.ckpt,
                         grad_compression=args.compress, peak_lr=args.lr,
                         log_every=max(1, args.steps // 20))
    out = Trainer(cfg, tcfg).run(resume=args.resume)
    for h in out["history"]:
        print(f"step {h['step']:>5}  loss {h['loss']:.4f}  {h['sec']:.2f}s")
    print(f"final loss: {out['final_loss']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
