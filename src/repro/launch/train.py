"""Training launcher: ``--arch`` x strategy on the local (or forced-count)
device mesh, driven through the ``repro.api.Session`` facade. For the
production 256/512-chip meshes use dryrun.py; this driver actually
executes steps: the registry config at its published widths, or the
reduced float32 variant with ``--smoke`` (what runs on a CPU).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b \
        --steps 50 --smoke                        # reduced variant, runs
    PYTHONPATH=src python -m repro.launch.train --arch minitron-4b --plan
"""
from __future__ import annotations

import argparse

from repro.api import Session, Strategy, TrainConfig, plan
from repro.configs import ARCH_NAMES, SHAPES, get_config, get_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="train the reduced float32 per-arch smoke config "
                         "(default: published widths in the config's dtype)")
    ap.add_argument("--plan", action="store_true",
                    help="print the planner's production-mesh strategy and "
                         "exit")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default="")
    args = ap.parse_args()

    if args.plan:
        cfg = get_config(args.arch)
        p = plan(cfg, SHAPES["train_4k"], 256, method="dp")
        print(f"{args.arch}: {p.summary()}")
        return

    enable_compile_cache()
    cfg = get_smoke(args.arch).with_(dtype="float32") if args.smoke \
        else get_config(args.arch)
    strategy = Strategy(remat=False, microbatches=args.microbatches,
                        seq_parallel=args.seq_parallel, fsdp=args.fsdp,
                        dtype=cfg.dtype)
    session = Session(cfg, strategy, make_host_mesh(model=1))
    tc = TrainConfig(steps=args.steps, lr=args.lr, log_every=10,
                     checkpoint_every=args.steps if args.checkpoint_dir
                     else 0,
                     checkpoint_dir=args.checkpoint_dir or "checkpoints")
    trainer = session.train(tc, global_batch=args.global_batch,
                            seq_len=args.seq)
    trainer.run()


if __name__ == "__main__":
    main()
