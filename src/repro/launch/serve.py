"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--reduced]``.

Spins up the batched prefill/decode engine on a model from the config
registry, with random weights made on the device from seed 0, and
serves a handful of synthetic equal-length requests.  Without
``--reduced`` the model has its published widths; ``chip_smoke.py`` at the
repo root drives this same path on a TPU.
"""
from __future__ import annotations

import argparse
import sys


def init_params(bundle, seed: int = 0, shardings=None):
    """Random weights from ``seed``, built under ``jax.jit`` so they are
    made on the device (placed by ``shardings`` when given)."""
    import jax
    return jax.jit(bundle.init, out_shardings=shardings)(
        jax.random.PRNGKey(seed))


def make_engine(cfg, *, batch_size: int, max_seq: int, seed: int = 0,
                shardings=None):
    """The launcher's engine: ``build(cfg)`` + seeded weights."""
    from ..models import build
    from ..serve import EngineConfig, ServeEngine
    bundle = build(cfg)
    params = init_params(bundle, seed, shardings)
    return ServeEngine(bundle, params, EngineConfig(batch_size=batch_size,
                                                    max_seq=max_seq))


def submit_prompts(engine, n: int, prompt_len: int, new_tokens: int,
                   seed: int = 0):
    """Queue ``n`` random prompts of ``prompt_len`` tokens (equal lengths:
    prefill has no padding mask)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return [engine.submit(rng.integers(0, engine.cfg.vocab_size - 1,
                                       size=prompt_len).astype(np.int32),
                          max_new_tokens=new_tokens) for _ in range(n)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()

    from ..configs import get_config
    from ..configs.base import reduce_for_smoke
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_for_smoke(cfg)
    engine = make_engine(cfg, batch_size=args.requests, max_seq=args.max_seq)
    submit_prompts(engine, args.requests, args.prompt_len, args.new_tokens)
    reqs = engine.run()
    for r in reqs:
        print(f"req {r.rid}: {r.out_tokens}")
    print(engine.stats)
    return 0


if __name__ == "__main__":
    sys.exit(main())
