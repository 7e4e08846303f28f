"""Training launcher.

CPU (this container): reduced configs on the synthetic corpus.
TPU pod: the same ``train_step`` with the production mesh + shardings —
``--dry-run`` lowers/compiles it without hardware (see dryrun.py).

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 100
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (paper-scale) config — only for "
                    "--dry-run or a real pod")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile train_4k on the production mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--save", default="",
                    help="checkpoint path (.npz) to write at the end")
    args = ap.parse_args()

    if args.dry_run:
        # dryrun must own process start-up (fake device flag)
        import os
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", args.arch, "--shape", "train_4k"]
        if args.multi_pod:
            cmd.append("--multi-pod")
        raise SystemExit(subprocess.call(cmd, env=dict(
            os.environ, PYTHONPATH=os.environ.get("PYTHONPATH", "src"))))

    from repro.launch import compile_cache
    compile_cache.enable()

    from repro.checkpoint import io as ckpt
    from repro.configs import get_config, get_smoke_config
    from repro.data import synthetic
    from repro.train import loop as TL

    cfg = (get_config(args.arch) if args.full_config
           else get_smoke_config(args.arch, vocab=synthetic.VOCAB))
    print(f"training {cfg.name} ({cfg.arch_type}), params "
          f"{cfg.param_count():,}")
    corpus = synthetic.SyntheticCorpus()
    stream = synthetic.token_stream(corpus, 300)
    it = synthetic.batches(stream, batch=args.batch, seq=args.seq)
    params, hist = TL.fit(cfg, it, steps=args.steps, log_every=20)
    print(f"final loss {hist[-1]:.4f}")
    if args.save:
        ckpt.save(args.save, params)
        print(f"saved {args.save}")


if __name__ == "__main__":
    main()
