"""Analytic, implementation-faithful FLOP and HBM-byte model per cell.

Why analytic: XLA's cost_analysis counts while-loop (lax.scan) bodies once,
and fully unrolling 48-layer x 128-chunk scans to calibrate it is
prohibitive on this 1-core container (measured).  The step functions are
closed-form op graphs, so we count them *exactly as implemented*:

  * attention (train/prefill): the chunked jnp path evaluates every
    (q, kv) block — causal masking does NOT skip work — so the count is the
    full S^2 term.  ``kernelized=True`` halves it (the Pallas flash kernel
    skips masked blocks); that delta is a §Perf lever, not the baseline.
  * remat: scanned blocks run forward twice (fwd + recompute) + backward
    (2x fwd)  =>  train multiplier 4x forward.
  * MoE: capacity-padded routed tokens (T*top_k*capacity_factor), + shared
    experts + router, matching the EP shard_map implementation.
  * bytes: a *kernelized TPU memory model* — params/grads/optimizer traffic,
    per-layer saved activations (remat boundaries), flash-style streaming
    attention (scores never round-trip HBM), KV-cache reads for decode.

Cross-validation: tests/test_roofline.py checks the analytic FLOPs against
XLA cost_analysis on small unrolled dense cells (within tolerance); the
dry-run records both where calibration is available.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from ..configs.base import ModelConfig
from ..configs.shapes import ShapeSpec

DT = 2      # bf16 bytes
F32 = 4


def _attn_core_flops(B: int, Sq: int, Skv: int, H: int, hd: int,
                     window: int = 0, kernelized: bool = False) -> float:
    """scores + AV matmuls.  Full-S^2 for the jnp chunked path."""
    if window:
        band = min(window + 512, Skv)   # banded gather width (cq=512)
        eff = band
    else:
        eff = Skv / 2 if kernelized else Skv
    return 2.0 * 2.0 * B * H * Sq * eff * hd


def _ssd_flops(cfg: ModelConfig, B: int, S: int) -> float:
    """Projections + conv + chunked SSD core (per layer)."""
    d, di = cfg.d_model, cfg.d_inner
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    q = min(cfg.ssm_chunk, S)
    proj = 2.0 * B * S * d * (2 * di + 2 * N + H)      # z, x, B, C, dt
    outp = 2.0 * B * S * di * d
    conv = 2.0 * B * S * (di + 2 * N) * cfg.ssm_conv_width
    scores = 2.0 * B * S * q * N                        # C B^T per chunk
    y_diag = 2.0 * B * S * q * H * P                    # (L*scores) @ xdt
    y_off = 2.0 * B * S * H * P * N
    state = 2.0 * B * S * H * P * N
    return proj + outp + conv + scores + y_diag + y_off + state


def _layer_flops_full(cfg: ModelConfig, B: int, S: int, kind: str,
                      kernelized: bool) -> float:
    """One layer, full-sequence forward."""
    d, hd = cfg.d_model, cfg.head_dim_
    H, KV = cfg.n_heads, cfg.n_kv_heads
    f = 0.0
    if kind in ("dense", "moe", "hybrid", "encoder", "decoder"):
        f += 2.0 * B * S * d * (H * hd + 2 * KV * hd)        # qkv
        f += 2.0 * B * S * H * hd * d                        # out proj
        f += _attn_core_flops(B, S, S, H, hd,
                              window=cfg.sliding_window, kernelized=kernelized)
    if kind == "decoder":  # whisper cross-attention
        f += 2.0 * B * S * d * H * hd + 2.0 * B * cfg.enc_seq * d * 2 * KV * hd
        f += 2.0 * B * S * H * hd * d
        f += _attn_core_flops(B, S, cfg.enc_seq, H, hd)
    if kind in ("ssm", "hybrid", "mamba"):
        f += _ssd_flops(cfg, B, S)
    if kind in ("dense", "hybrid", "encoder", "decoder", "mamba"):
        n_mats = 3 if cfg.mlp == "swiglu" else 2
        f += 2.0 * B * S * d * cfg.d_ff * n_mats
    if kind == "moe":
        T = B * S
        f += 2.0 * T * d * cfg.n_experts                      # router
        routed_tok = T * cfg.top_k * cfg.capacity_factor      # capacity pad
        f += 2.0 * routed_tok * d * cfg.moe_d_ff * 3
        f += 2.0 * T * d * (cfg.n_shared_experts * cfg.moe_d_ff) * 3
    return f


def _layer_flops_decode(cfg: ModelConfig, B: int, S_cache: int,
                        kind: str) -> float:
    d, hd = cfg.d_model, cfg.head_dim_
    H, KV = cfg.n_heads, cfg.n_kv_heads
    f = 0.0
    if kind in ("dense", "moe", "hybrid", "decoder"):
        f += 2.0 * B * d * (H * hd + 2 * KV * hd) + 2.0 * B * H * hd * d
        eff = min(S_cache, cfg.sliding_window) if cfg.sliding_window else S_cache
        f += 2.0 * 2.0 * B * H * eff * hd
    if kind == "decoder":
        f += 2.0 * B * d * H * hd + 2.0 * B * H * hd * d
        f += 2.0 * 2.0 * B * H * cfg.enc_seq * hd
    if kind in ("ssm", "hybrid", "mamba"):
        di, P, N, Hs = cfg.d_inner, cfg.ssm_headdim, cfg.ssm_state, cfg.ssm_heads
        f += 2.0 * B * d * (2 * di + 2 * N + Hs) + 2.0 * B * di * d
        f += 2.0 * B * Hs * P * N * 2
    if kind in ("dense", "hybrid", "decoder", "mamba"):
        n_mats = 3 if cfg.mlp == "swiglu" else 2
        f += 2.0 * B * d * cfg.d_ff * n_mats
    if kind == "moe":
        f += 2.0 * B * d * cfg.n_experts
        f += 2.0 * B * cfg.top_k * d * cfg.moe_d_ff * 3
        f += 2.0 * B * d * cfg.n_shared_experts * cfg.moe_d_ff * 3
    return f


def _layer_kinds(cfg: ModelConfig):
    from ..models.lm import layer_plan
    if cfg.family == "encdec":
        return [("encoder", cfg.n_enc_layers), ("decoder", cfg.n_layers)]
    out = []
    for kinds, count in layer_plan(cfg):
        for k in kinds:
            out.append((k, count))
    return out


def step_flops(cfg: ModelConfig, shape: ShapeSpec, *,
               kernelized: bool = False) -> float:
    """Whole-step FLOPs for the cell, as implemented."""
    B, S = shape.global_batch, shape.seq_len
    Vp, d = cfg.padded_vocab, cfg.d_model
    if shape.kind == "decode":
        f = 2.0 * B * d * Vp  # lm head (embed gather ~ 0 flops)
        for kind, count in _layer_kinds(cfg):
            if kind == "encoder":
                continue
            f += count * _layer_flops_decode(cfg, B, S, kind)
        return f
    S_text = S - cfg.frontend_seq if cfg.family == "vlm" else S
    f = 2.0 * B * S_text * d * Vp
    for kind, count in _layer_kinds(cfg):
        Sk = cfg.enc_seq if kind == "encoder" else S
        f += count * _layer_flops_full(cfg, B, Sk, kind, kernelized)
    if shape.kind == "train":
        f *= 4.0  # fwd + remat fwd + bwd(2x)
    return f


def step_bytes(cfg: ModelConfig, shape: ShapeSpec, *,
               moment_dtype: str = "float32") -> float:
    """Kernelized HBM byte model (whole step, all chips summed)."""
    B, S = shape.global_batch, shape.seq_len
    d = cfg.d_model
    P = cfg.param_count()
    pbytes = P * DT
    mom = P * (1 if moment_dtype == "int8" else F32) * 2
    if shape.kind == "decode":
        # params once, caches read+slot write, small activations
        total = pbytes
        KV, hd = cfg.n_kv_heads, cfg.head_dim_
        for kind, count in _layer_kinds(cfg):
            if kind in ("dense", "moe", "hybrid", "decoder"):
                eff = min(S, cfg.sliding_window) if cfg.sliding_window else S
                total += count * B * eff * 2 * KV * hd * DT   # cache read
            if kind == "decoder":
                total += count * B * cfg.enc_seq * 2 * KV * hd * DT
            if kind in ("ssm", "hybrid", "mamba"):
                total += count * B * cfg.ssm_heads * cfg.ssm_headdim * \
                    cfg.ssm_state * F32 * 2                    # state r/w
        total += B * cfg.padded_vocab * DT                     # logits
        return total
    # train / prefill
    n_layers_total = sum(c for _, c in _layer_kinds(cfg))
    act = n_layers_total * B * S * d * DT                      # saved acts
    qkv_stream = 0.0
    for kind, count in _layer_kinds(cfg):
        Sk = cfg.enc_seq if kind == "encoder" else S
        width = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim_ \
            if kind not in ("ssm", "mamba") else 2 * cfg.d_inner
        qkv_stream += count * B * Sk * width * DT * 2          # r + w
    logits = B * S * cfg.padded_vocab * F32
    if shape.kind == "prefill":
        return pbytes + act * 2 + qkv_stream + logits
    # train: params read 3x (fwd/remat/bwd) + grads w + update r/w + moments
    return pbytes * 3 + pbytes * 2 + mom * 2 + act * 4 + qkv_stream * 3 + \
        logits * 2
