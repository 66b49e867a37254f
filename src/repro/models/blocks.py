"""Transformer/SSM/hybrid/MoE blocks, composed from attention/ssd/moe.

Block kinds (one per layer, from ``ModelConfig.block_kinds``):

  dense   : norm -> attn -> +res ; norm -> mlp  -> +res
  moe     : norm -> attn -> +res ; norm -> moe  -> +res   (+ shared experts)
  ssm     : norm -> ssd  -> +res                           (mamba2: no FFN)
  mamba   : norm -> ssd  -> +res ; norm -> mlp  -> +res   (granite-4.0-h)
  hybrid  : norm -> (attn || ssd) -> +res ; norm -> mlp -> +res   (hymba)
  encoder : norm -> bidir attn -> +res ; norm -> mlp -> +res      (whisper)
  decoder : norm -> causal attn -> +res ; norm -> cross-attn -> +res ;
            norm -> mlp -> +res                                   (whisper)

Every residual branch is scaled by ``cfg.residual_multiplier`` (granite's
muP; 1 elsewhere).  Every init returns (params, logical_axes).  Every
forward threads an optional per-layer cache (decode) and an aux-loss
accumulator (MoE load balance).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (attention_forward, attention_init, fuse_heads,
                        init_kv_cache)
from .common import Params, apply_norm, dense_init, norm_init
from .moe import moe_forward, moe_init
from .ssd import init_ssd_cache, ssd_decode_step, ssd_forward, ssd_init
from .common import get_mesh_context


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(cfg, key, dtype) -> Tuple[Params, Dict]:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp == "swiglu":
        p = {"wg": dense_init(ks[0], (d, f), dtype),
             "wu": dense_init(ks[1], (d, f), dtype),
             "wd": dense_init(ks[2], (f, d), dtype, in_axis=0)}
        ax = {"wg": ("embed", "ff"), "wu": ("embed", "ff"),
              "wd": ("ff", "embed")}
    else:  # gelu (whisper)
        p = {"w1": dense_init(ks[0], (d, f), dtype),
             "b1": jnp.zeros((f,), dtype),
             "w2": dense_init(ks[1], (f, d), dtype, in_axis=0),
             "b2": jnp.zeros((d,), dtype)}
        ax = {"w1": ("embed", "ff"), "b1": ("ff",),
              "w2": ("ff", "embed"), "b2": ("embed",)}
    return p, ax


def mlp_forward(cfg, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.mlp == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["wg"])
        u = jnp.einsum("bsd,df->bsf", x, p["wu"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        return jnp.einsum("bsf,fd->bsd", h, p["wd"])
    h = jnp.einsum("bsd,df->bsf", x, p["w1"]) + p["b1"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, p["w2"]) + p["b2"]


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def residual(cfg, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """``x`` plus the branch ``y`` scaled by the residual multiplier."""
    m = cfg.residual_multiplier
    return x + (y if m == 1.0 else y * m)


def block_init(cfg, key, dtype, kind: str) -> Tuple[Params, Dict]:
    ks = jax.random.split(key, 8)
    p: Params = {}
    ax: Dict = {}
    if kind in ("dense", "moe", "hybrid", "encoder", "decoder"):
        p["ln1"], ax["ln1"] = norm_init(cfg, cfg.d_model, dtype)
        p["attn"], ax["attn"] = attention_init(cfg, ks[0], dtype)
    if kind == "hybrid":
        p["ssd"], ax["ssd"] = ssd_init(cfg, ks[1], dtype)
    if kind in ("ssm", "mamba"):
        p["ln1"], ax["ln1"] = norm_init(cfg, cfg.d_model, dtype)
        p["ssd"], ax["ssd"] = ssd_init(cfg, ks[1], dtype)
    if kind == "decoder":
        p["ln_cross"], ax["ln_cross"] = norm_init(cfg, cfg.d_model, dtype)
        p["cross"], ax["cross"] = attention_init(cfg, ks[2], dtype, cross=True)
    if kind in ("dense", "hybrid", "encoder", "decoder", "mamba"):
        p["ln2"], ax["ln2"] = norm_init(cfg, cfg.d_model, dtype)
        p["mlp"], ax["mlp"] = mlp_init(cfg, ks[3], dtype)
    if kind == "moe":
        p["ln2"], ax["ln2"] = norm_init(cfg, cfg.d_model, dtype)
        p["moe"], ax["moe"] = moe_init(cfg, ks[3], dtype)
    return p, ax


def block_forward(cfg, p: Params, x: jnp.ndarray, kind: str, *,
                  cache: Optional[Dict] = None,
                  cache_pos: Optional[jnp.ndarray] = None,
                  enc_out: Optional[jnp.ndarray] = None,
                  window_override: Optional[int] = None,
                  layer: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Returns (y, new_cache, aux_loss).  ``cache`` is this layer's slice,
    but for the SSM state: the stack's whole state, of which the step
    rewrites layer ``layer`` (``ssd_decode_step``).

    In decode the returned 'cache' holds what the step writes: the new
    token's K/V row, the stack's state with this layer's rewritten, and
    the new conv tails; cross-attention K/V are read and not returned.  In
    full (train/prefill) mode it holds the K/V computed for the sequence
    (prefill seeds the decode cache from it); SSM blocks return their final
    state + conv tails.
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    window = cfg.sliding_window if window_override is None else window_override
    decoding = cache is not None and x.shape[1] == 1

    if kind in ("ssm", "mamba"):
        h = apply_norm(cfg, x, p["ln1"])
        if decoding:
            y, new_cache = ssd_decode_step(cfg, p["ssd"], h, cache, layer)
        else:
            y, new_cache = ssd_forward(cfg, p["ssd"], h, return_state=True)
        x = residual(cfg, x, y)
        if kind == "ssm":
            return x, new_cache, aux
        h = apply_norm(cfg, x, p["ln2"])
        return residual(cfg, x, mlp_forward(cfg, p["mlp"], h)), new_cache, aux

    # --- attention sub-block ---
    h = apply_norm(cfg, x, p["ln1"])
    causal = kind != "encoder"
    if decoding:
        attn_cache = {"k": cache["k"], "v": cache["v"]}
        y_attn, kv = attention_forward(
            cfg, p["attn"], h, causal=causal, window=window,
            use_rope=cfg.rope, cache=attn_cache, cache_pos=cache_pos)
        new_cache.update(kv)
    else:
        y_attn, kv = attention_forward(
            cfg, p["attn"], h, causal=causal, window=window,
            use_rope=cfg.rope)
        if kv is not None:
            new_cache["k"], new_cache["v"] = map(fuse_heads, kv)

    if kind == "hybrid":
        if decoding:
            ssd_cache = {k: cache[k] for k in ("state", "conv_x", "conv_BC")}
            y_ssd, ssd_new = ssd_decode_step(cfg, p["ssd"], h, ssd_cache,
                                             layer)
            new_cache.update(ssd_new)
        else:
            y_ssd, ssd_new = ssd_forward(cfg, p["ssd"], h, return_state=True)
            new_cache.update(ssd_new)
        # hymba: fuse the parallel attention and SSM head outputs
        y_attn = 0.5 * (y_attn + y_ssd)
    x = residual(cfg, x, y_attn)

    if kind == "decoder":
        h = apply_norm(cfg, x, p["ln_cross"])
        if decoding:
            y_cross, _ = attention_forward(
                cfg, p["cross"], h,
                precomputed_kv=(cache["cross_k"], cache["cross_v"]))
        else:
            y_cross, ckv = attention_forward(cfg, p["cross"], h,
                                             kv_x=enc_out, causal=False,
                                             use_rope=False)
            if ckv is not None:
                new_cache["cross_k"], new_cache["cross_v"] = map(
                    fuse_heads, ckv)
        x = residual(cfg, x, y_cross)

    # --- FFN sub-block ---
    h = apply_norm(cfg, x, p["ln2"])
    if kind == "moe":
        mesh, data_spec, model_axis = get_mesh_context()
        y, aux = moe_forward(cfg, p["moe"], h, mesh=mesh,
                             data_spec=data_spec, model_axis=model_axis)
    else:
        y = mlp_forward(cfg, p["mlp"], h)
    return residual(cfg, x, y), new_cache, aux


def init_block_cache(cfg, kind: str, batch: int, max_seq: int, dtype) -> Dict:
    """Decode-cache structure for one layer of the given kind."""
    c: Dict[str, Any] = {}
    if kind in ("dense", "moe", "hybrid", "decoder", "encoder"):
        c.update(init_kv_cache(cfg, batch, max_seq, dtype))
    if kind in ("ssm", "hybrid", "mamba"):
        c.update(init_ssd_cache(cfg, batch, dtype))
    return c
