"""Training launcher: mesh setup + sharded train loop.

    python -m repro.launch.train --arch smollm-135m --steps 200 \
        --data-parallel 1 --model-parallel 1 --batch 8 --seq 128

On a single CPU host this runs a reduced config end-to-end (real training,
loss must fall); on TPU pods the same entry point builds the production
mesh and shards state via the same rules the dry-run compiles (the dry-run
IS this launcher's compile path).  Fault tolerance: auto-resume from the
newest committed checkpoint + restart supervision (distributed.fault).
"""

from __future__ import annotations

import argparse
import logging

import jax
import numpy as np


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--attention", default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="use the reduced per-family config (CPU scale)")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-restarts", type=int, default=2)
    return ap.parse_args(argv)


def run(args: argparse.Namespace) -> dict:
    """Train as the parsed arguments say; returns the loop's result
    (``params``, ``opt_state``, ``history``)."""
    log = logging.getLogger("repro.launch.train")

    from repro.checkpoint import CheckpointConfig
    from repro.configs import get_config
    from repro.data.pipeline import PipelineConfig, lm_batch_at
    from repro.distributed.fault import SupervisorConfig, run_supervised
    from repro.distributed.sharding import use_mesh
    from repro.launch.mesh import make_mesh
    from repro.models.registry import get_model
    from repro.optim import AdamWConfig, warmup_cosine
    from repro.train.loop import TrainConfig, train

    name = args.arch if not args.attention else f"{args.arch}@{args.attention}"
    cfg = get_config(name)
    if args.reduced:
        cfg = cfg.reduced()
    api = get_model(cfg)

    pipe = PipelineConfig(global_batch=args.batch, seq_len=args.seq,
                          vocab_size=cfg.vocab_size, seed=args.seed)
    opt_cfg = AdamWConfig(lr=warmup_cosine(args.lr, 10, args.steps))
    train_cfg = TrainConfig(
        total_steps=args.steps, seed=args.seed,
        checkpoint=(CheckpointConfig(args.ckpt_dir,
                                     every_steps=args.ckpt_every)
                    if args.ckpt_dir else None))

    def batch_fn(step):
        return lm_batch_at(pipe, step)

    dp, mp = args.data_parallel, args.model_parallel
    n_dev = len(jax.devices())
    if dp * mp > n_dev:
        raise SystemExit(f"mesh {dp}x{mp} needs {dp*mp} devices, "
                         f"have {n_dev}")

    result = {}

    def attempt_once(attempt):
        log.info("attempt %d: training %s for %d steps on %dx%d mesh",
                 attempt, cfg.name, args.steps, dp, mp)
        if dp * mp > 1:
            mesh = make_mesh(dp, mp)
            with use_mesh(mesh):
                result.update(train(api, opt_cfg, train_cfg, batch_fn))
        else:
            result.update(train(api, opt_cfg, train_cfg, batch_fn))

    run_supervised(attempt_once,
                   SupervisorConfig(max_restarts=args.max_restarts))
    hist = result["history"]
    if hist:
        log.info("final loss %.4f (first %.4f)", hist[-1]["loss"],
                 hist[0]["loss"])
    return result


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = parse_args(argv)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    run(args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
