"""Real training launcher.

On a TPU fleet this binary runs per host (jax.distributed.initialize picks up
the pod runtime); on CPU it runs the reduced config end-to-end. The dry-run
path (launch/dryrun.py) is the no-hardware twin of this launcher — both build
the same step through train.step.make_train_step.

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch granite_8b --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--error-feedback", action="store_true",
                    help="carry the int8 compression residual across steps "
                         "(EF-SGD; implies --compress-grads semantics)")
    ap.add_argument("--model-parallel", type=int, default=0,
                    help="build a (data, model) host mesh with this model-"
                         "axis size and train under use_sharding")
    ap.add_argument("--seq-shard", action="store_true",
                    help="let leftover model axis land on the sequence dim")
    ap.add_argument("--distributed", action="store_true",
                    help="multi-host: call jax.distributed.initialize()")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.distributed:
        import jax
        jax.distributed.initialize()

    from repro.data import DataConfig
    from repro.models.registry import get_config
    from repro.optim import AdamWConfig
    from repro.train import Trainer

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    data_cfg = DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch
    )
    mesh = None
    if args.model_parallel:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model=args.model_parallel)
    trainer = Trainer(
        cfg,
        data_cfg,
        opt_cfg=AdamWConfig(lr=args.lr, total_steps=args.steps),
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        microbatches=args.microbatches,
        compress_grads=args.compress_grads or args.error_feedback,
        error_feedback=args.error_feedback,
        mesh=mesh,
        sharding_rules={"seq": (("model",), ())} if args.seq_shard else None,
    )
    history = trainer.run(args.steps)
    print(f"final loss {history[-1]:.4f} (start {history[0]:.4f}); "
          f"stragglers: {trainer.stragglers}")


if __name__ == "__main__":
    main()
