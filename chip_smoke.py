#!/usr/bin/env python3
"""Chip smoke test: serve full-width models on a TPU through ServeEngine.

    python chip_smoke.py               # one chip: qwen2_0_5b
    python chip_smoke.py --four-chips  # v5e 2x2 host: one vNPU tenant

One chip: full-width qwen2_0_5b (24 layers, d_model 896, vocab 151,936,
bf16) with random weights made on the device from a seed, served through
the launcher's ``make_engine`` / ``ServeEngine``: 8 prompts of 512 tokens,
64 new tokens each, max_seq 1024.  Every served sequence, teacher-forced
through ``bundle.forward``, must give the engine's greedy tokens wherever
the top-2 logit margin is above bf16 noise.  A float32 copy of the same
config then serves one of those prompts, and its teacher-forced greedy
tokens must equal all of the served ones.

Four chips: a 2x2 vNPU tenant placed by ``VNPUPolicy`` on the host's ICI
grid becomes a JAX mesh.  A 2-layer qwen2_7b (published widths) served
through ``make_engine`` gives the same logits and the same tokens on that
mesh as on one chip, then full-width qwen2_7b is served on the mesh with
its weights sharded over the four chips.

Every phase raises on failure.  The script runs in one process and starts
no other (a chip belongs to one process).  It exits non-zero, printing no
result, where JAX finds no TPU.  The last line of stdout is a JSON object
naming the device.  Times printed are smoke timings, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import DeviceTopology  # noqa: E402
from repro.core.vmesh import virtual_mesh  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import make_engine, submit_prompts  # noqa: E402
from repro.models import build  # noqa: E402
from repro.models.common import (clear_mesh_context,  # noqa: E402
                                 set_activation_rules, set_mesh_context)
from repro.parallel import sharding as shd  # noqa: E402
from repro.sched import TenantSpec, VNPUPolicy  # noqa: E402

ARCH, N_REQ, PROMPT, NEW, MAX_SEQ, SEED = "qwen2_0_5b", 8, 512, 64, 1024, 0
# four-chip phase: full-width qwen2_7b tenant, and its 2-layer cut
ARCH4, N_REQ4, PROMPT4, NEW4, MAX_SEQ4 = "qwen2_7b", 4, 128, 16, 256
CUT_LAYERS = 2
# the 2-layer logits on the tenant mesh vs one chip, float32 at "highest"
# matmul precision: only the order of sharded reductions differs
MESH_RTOL = MESH_ATOL = 2e-3
# bf16 engine vs bf16 teacher-forced forward: a token is checked where the
# forward's top-2 margin exceeds this, about twice the largest bf16-vs-
# float32 logit difference (0.048) seen on qwen2_0_5b's served sequences
BF16_MARGIN = 0.1
HBM_PER_CHIP = 16 << 30


def require_tpu(n: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX backend: {devs[0].platform})"
                 "; nothing was run")
    if len(devs) < n:
        sys.exit(f"chip_smoke: needs {n} TPU chips, found {len(devs)}")
    return devs


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def mem(dev, key: str = "peak_bytes_in_use") -> int:
    return dev.memory_stats()[key]


def programs(engine) -> int:
    """Compiles (or compile-cache loads) of the engine's two steps: 2 means
    the ahead-of-time compile served every call."""
    return engine.stats["compiles"]


def check_served(reqs, n_new: int, vocab: int) -> None:
    for r in reqs:
        if not (r.done and len(r.out_tokens) == n_new
                and all(0 <= t < vocab for t in r.out_tokens)):
            raise AssertionError(f"request {r.rid} not served: done={r.done}"
                                 f" tokens={r.out_tokens}")


def serve_one_chip(dev):
    """Full-width bf16 serving; returns the engine and its requests."""
    cfg = get_config(ARCH)
    engine = make_engine(cfg, batch_size=N_REQ, max_seq=MAX_SEQ, seed=SEED)
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, vocab "
        f"{cfg.vocab_size}, {cfg.param_dtype}, "
        f"{cfg.param_count() / 1e6:.1f}M params")
    comp = engine.compile(PROMPT)
    log(f"compile s: prefill {comp['prefill_s']} decode {comp['decode_s']}")
    # warm-up batch of the served shapes, then the measured batch
    submit_prompts(engine, N_REQ, PROMPT, 2, SEED + 1)
    engine.run()
    before = dict(engine.stats)
    reqs = submit_prompts(engine, N_REQ, PROMPT, NEW, SEED)
    engine.run()
    check_served(reqs, NEW, cfg.vocab_size)
    st = {k: engine.stats[k] - before[k] for k in before}
    log(f"served {len(reqs)} requests x {PROMPT} prompt tokens, "
        f"{st['tokens_out']} tokens out, {st['decode_steps']} decode steps")
    log(f"smoke timing, not a metric: prefill {N_REQ}x{PROMPT} "
        f"{st['prefill_s']} s; decode {st['decode_s'] / st['decode_steps']}"
        f" s/token-step at batch {N_REQ}")
    log(f"peak_bytes_in_use {mem(dev)}")
    log(f"step compiles: {programs(engine)}")
    if programs(engine) != 2:
        raise AssertionError("the engine recompiled while serving")
    return engine, reqs


def check_bf16_served(engine, reqs) -> None:
    """Teacher-force every served sequence through ``bundle.forward``: its
    greedy tokens must be the engine's wherever the margin is clear of
    bf16 noise, and at least an eighth of the tokens must be that clear."""
    V = engine.cfg.vocab_size
    seqs = np.stack([np.concatenate([r.prompt, r.out_tokens[:-1]])
                     for r in reqs])

    @jax.jit
    def top2(params, tokens):
        logits = engine.bundle.forward(params, {"tokens": tokens})
        vals, idx = jax.lax.top_k(
            logits[:, PROMPT - 1:, :V].astype(jnp.float32), 2)
        return idx[..., 0], vals[..., 0] - vals[..., 1]

    greedy, margin = map(np.asarray, top2(engine.params,
                                          jnp.asarray(seqs, jnp.int32)))
    served = np.array([r.out_tokens for r in reqs])
    clear = margin > BF16_MARGIN
    differ = greedy != served
    bad = np.argwhere(clear & differ)
    log(f"bf16 engine vs teacher-forced bf16 forward, {len(reqs)} requests: "
        f"{int((~differ).sum())}/{served.size} tokens equal, largest top-2 "
        f"margin where they differ {float(margin[differ].max(initial=0))}; "
        f"{int(clear.sum())} with margin > {BF16_MARGIN}, {len(bad)} of "
        f"them differ")
    if len(bad):
        raise AssertionError(f"bf16 served tokens differ from the forward's "
                             f"at (request, step) {bad[:8].tolist()}")
    if clear.sum() < served.size // 8:
        raise AssertionError("too few tokens clear of bf16 noise to check")


def check_float32_reference(engine, req) -> None:
    """A float32 copy serves ``req``'s prompt; teacher-forcing its output
    through ``bundle.forward`` must reproduce it greedily."""
    cfg32 = dataclasses.replace(engine.cfg, param_dtype="float32",
                                compute_dtype="float32")
    V = cfg32.vocab_size
    with jax.default_matmul_precision("highest"):
        eng32 = make_engine(cfg32, batch_size=1, max_seq=MAX_SEQ, seed=SEED)
        r32 = eng32.submit(req.prompt, max_new_tokens=NEW)
        eng32.run()
        check_served([r32], NEW, V)
        seq = np.concatenate([r32.prompt, r32.out_tokens[:-1]])[None]
        logits32 = jax.jit(eng32.bundle.forward)(
            eng32.params, {"tokens": jnp.asarray(seq, jnp.int32)})
        logits32 = np.asarray(logits32[0, PROMPT - 1:, :V], np.float32)
    greedy = logits32.argmax(-1).tolist()
    if greedy != r32.out_tokens:
        bad = [i for i, (a, b) in enumerate(zip(greedy, r32.out_tokens))
               if a != b]
        raise AssertionError(f"float32 engine vs teacher-forced forward: "
                             f"tokens differ at steps {bad[:8]}")
    log(f"float32 engine tokens == teacher-forced forward greedy tokens "
        f"({NEW}/{NEW})")
    logits16 = jax.jit(engine.bundle.forward)(
        engine.params, {"tokens": jnp.asarray(seq, jnp.int32)})
    logits16 = np.asarray(logits16[0, PROMPT - 1:, :V], np.float32)
    if not np.isfinite(logits16).all():
        raise AssertionError("bf16 logits are not finite")
    diff = float(np.abs(logits16 - logits32).max())
    agree = int((logits16.argmax(-1) == logits32.argmax(-1)).sum())
    served = sum(a == b for a, b in zip(req.out_tokens, r32.out_tokens))
    log(f"bf16 vs float32 forward on the served sequence: max |dlogit| "
        f"{diff} (float32 |logit| max {float(np.abs(logits32).max())}), "
        f"greedy agreement {agree}/{NEW}; bf16 engine request {req.rid} "
        f"matches float32 engine on {served}/{NEW} tokens")


# ---------------------------------------------------------------------------
# four chips: one vNPU tenant over the 2x2 host
# ---------------------------------------------------------------------------

def tenant_mesh(devices, memory_bytes: int):
    """Place a tenant of ``len(devices)`` cores through the vNPU policy and
    materialize it as a (data, model) mesh."""
    dt = DeviceTopology.from_devices(devices)
    policy = VNPUPolicy(dt.topo, hbm_bytes=len(devices) * HBM_PER_CHIP)
    placement = policy.allocate(TenantSpec(
        tid=1, model=ARCH4, n_cores=len(devices), arrival_s=0.0,
        duration_s=math.inf, memory_bytes=memory_bytes))
    return virtual_mesh(placement.vnpu, dt)


def install_mesh(mesh, bundle):
    """Mesh context + activation rules for the model; returns the param
    shardings."""
    set_mesh_context(mesh, shd.batch_axes(mesh))
    set_activation_rules(shd.activation_rules(mesh))
    return shd.named_shardings(mesh, shd.param_specs(
        bundle.param_logical_axes(), shd.param_rules(mesh)))


def serve_cut(cfg, shardings=None):
    """Serve ``cfg`` through ``make_engine``; returns the forward logits
    of the prompts and the served tokens."""
    engine = make_engine(cfg, batch_size=N_REQ4, max_seq=MAX_SEQ4, seed=SEED,
                         shardings=shardings)
    reqs = submit_prompts(engine, N_REQ4, PROMPT4, NEW4, SEED)
    tokens = jnp.asarray(np.stack([r.prompt for r in reqs]))
    logits = np.asarray(jax.jit(engine.bundle.forward)(
        engine.params, {"tokens": tokens}), np.float32)
    engine.run()
    check_served(reqs, NEW4, cfg.vocab_size)
    return logits, np.array([r.out_tokens for r in reqs])


def check_mesh_matches_one_chip(mesh) -> None:
    cfg = dataclasses.replace(get_config(ARCH4), n_layers=CUT_LAYERS,
                              param_dtype="float32", compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        clear_mesh_context()
        ref, ref_tok = serve_cut(cfg)
        gc.collect()             # free the one-chip weights before the mesh's
        with jax.set_mesh(mesh):
            out, tok = serve_cut(cfg, install_mesh(mesh, build(cfg)))
        gc.collect()
        clear_mesh_context()
    diff = float(np.abs(out - ref).max())
    log(f"{ARCH4} cut to {CUT_LAYERS} layers, float32: tenant mesh vs one "
        f"chip max |dlogit| {diff} (|logit| max {float(np.abs(ref).max())},"
        f" rtol=atol={MESH_RTOL}); served tokens equal "
        f"{int((tok == ref_tok).sum())}/{tok.size}")
    np.testing.assert_allclose(out, ref, rtol=MESH_RTOL, atol=MESH_ATOL)
    if not np.array_equal(tok, ref_tok):
        raise AssertionError("the tenant mesh served other tokens than one "
                             "chip")


def serve_on_mesh(mesh) -> None:
    cfg = get_config(ARCH4)
    with jax.set_mesh(mesh):
        pshard = install_mesh(mesh, build(cfg))
        engine = make_engine(cfg, batch_size=N_REQ4, max_seq=MAX_SEQ4,
                             seed=SEED, shardings=pshard)
        jax.block_until_ready(engine.params)
        shares = {d.id: mem(d, "bytes_in_use") for d in mesh.devices.flat}
        log(f"{ARCH4} full width ({cfg.param_count() / 1e9:.2f}B params, "
            f"{cfg.param_dtype}) placed; bytes_in_use per device {shares}")
        if max(shares.values()) > 0.5 * sum(shares.values()):
            raise AssertionError(f"weights not spread: {shares}")
        comp = engine.compile(PROMPT4)
        log(f"compile s: prefill {comp['prefill_s']} decode "
            f"{comp['decode_s']}")
        submit_prompts(engine, N_REQ4, PROMPT4, 2, SEED + 1)   # warm-up
        engine.run()
        before = dict(engine.stats)
        reqs = submit_prompts(engine, N_REQ4, PROMPT4, NEW4, SEED)
        engine.run()
    clear_mesh_context()
    check_served(reqs, NEW4, cfg.vocab_size)
    st = {k: engine.stats[k] - before[k] for k in before}
    log(f"served {len(reqs)} requests x {PROMPT4} prompt tokens on the "
        f"tenant mesh, {st['tokens_out']} tokens out")
    log(f"smoke timing, not a metric: prefill {N_REQ4}x{PROMPT4} "
        f"{st['prefill_s']} s; decode "
        f"{st['decode_s'] / st['decode_steps']} s/token-step")
    log("peak_bytes_in_use per device " + str(
        {d.id: mem(d) for d in mesh.devices.flat}))
    log(f"step compiles: {programs(engine)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 vNPU tenant phase (4 chips)")
    args = ap.parse_args()

    n = 4 if args.four_chips else 1
    devs = require_tpu(n)
    log(f"cache dir {enable_compile_cache()}")
    log(f"device {devs[0].platform} / {devs[0].device_kind}, "
        f"{len(devs)} visible")
    t0 = time.perf_counter()
    if args.four_chips:
        mesh = tenant_mesh(devs[:4],
                           memory_bytes=2 * get_config(ARCH4).param_count())
        log("tenant mesh (data, model) -> device coords: " + str(
            [[getattr(d, "coords", d.id) for d in row]
             for row in mesh.devices]))
        check_mesh_matches_one_chip(mesh)
        serve_on_mesh(mesh)
    else:
        engine, reqs = serve_one_chip(devs[0])
        check_bf16_served(engine, reqs)
        check_float32_reference(engine, reqs[0])
    log(f"wall {time.perf_counter() - t0} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
