"""Whisper-large-v3 backbone: encoder-decoder on the shared blocks.

The conv/mel frontend is a STUB per the assignment: the batch's ``frames``
are precomputed frame embeddings (B, enc_seq, d_model).  Learned
positional embeddings (sized to the assigned shapes — the real model stops
at 448 decoder positions; deviation noted in DESIGN.md), LayerNorm, GELU.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .blocks import block_forward, block_init, init_block_cache
from .common import (Params, apply_norm, dtype_of, embed_init, norm_init,
                     softmax_cross_entropy, with_logical_constraint)
from .lm import _scan_stack

MAX_DEC_POS = 32_768


def init_params(cfg, key) -> Tuple[Params, Dict]:
    dtype = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    d = cfg.d_model

    def stack(k, kind, count):
        def one(kk):
            bp, _ = block_init(cfg, kk, dtype, kind)
            return {"b0": bp}
        _, bx = block_init(cfg, jax.random.PRNGKey(0), dtype, kind)
        ax = {"b0": jax.tree.map(lambda t: ("layers",) + tuple(t), bx,
                                 is_leaf=lambda t: isinstance(t, tuple))}
        return jax.vmap(one)(jax.random.split(k, count)), ax

    enc, enc_ax = stack(ks[0], "encoder", cfg.n_enc_layers)
    dec, dec_ax = stack(ks[1], "decoder", cfg.n_layers)
    p = {
        "embed": embed_init(ks[2], cfg.padded_vocab, d, dtype),
        "pos_enc": (jax.random.normal(ks[3], (cfg.enc_seq, d), jnp.float32)
                    * 0.02).astype(dtype),
        "pos_dec": (jax.random.normal(ks[4], (MAX_DEC_POS, d), jnp.float32)
                    * 0.02).astype(dtype),
        "enc_stack": enc,
        "dec_stack": dec,
    }
    ax = {
        "embed": ("vocab", "embed"),
        "pos_enc": (None, "embed"),
        "pos_dec": (None, "embed"),
        "enc_stack": enc_ax,
        "dec_stack": dec_ax,
    }
    p["enc_norm"], ax["enc_norm"] = norm_init(cfg, d, dtype)
    p["final_norm"], ax["final_norm"] = norm_init(cfg, d, dtype)
    p["lm_head"] = embed_init(ks[5], cfg.padded_vocab, d, dtype).T
    ax["lm_head"] = ("embed", "vocab")
    return p, ax


def encode(cfg, p: Params, frames: jnp.ndarray) -> jnp.ndarray:
    x = frames.astype(p["pos_enc"].dtype) + p["pos_enc"][None]
    x = with_logical_constraint(x, "batch", None, None)
    x, _, _ = _scan_stack(cfg, p["enc_stack"], x, ("encoder",))
    return apply_norm(cfg, x, p["enc_norm"])


def forward(cfg, p: Params, batch: Dict[str, jnp.ndarray], *,
            collect_cache: bool = False):
    enc_out = encode(cfg, p, batch["frames"])
    tokens = batch["tokens"]
    S = tokens.shape[1]
    x = jnp.take(p["embed"], tokens, axis=0) + p["pos_dec"][None, :S]
    x = with_logical_constraint(x, "batch", None, None)
    x, ys, aux = _scan_stack(cfg, p["dec_stack"], x, ("decoder",),
                             collect_cache=collect_cache, enc_out=enc_out)
    x = apply_norm(cfg, x, p["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, p["lm_head"])
    logits = with_logical_constraint(logits, "batch", None, "vocab_act")
    return logits, ([ys] if collect_cache else None), aux


def loss_fn(cfg, p: Params, batch: Dict[str, jnp.ndarray]):
    logits, _, _ = forward(cfg, p, batch)
    ce = softmax_cross_entropy(logits[:, :-1, :], batch["tokens"][:, 1:],
                               cfg.vocab_size)
    loss = jnp.mean(ce)
    return loss, {"loss": loss, "ce": loss}


def init_cache(cfg, batch: int, max_seq: int) -> List[Any]:
    dtype = dtype_of(cfg.param_dtype)
    KV, hd = cfg.n_kv_heads, cfg.head_dim_

    def one(_):
        c = init_block_cache(cfg, "decoder", batch, max_seq, dtype)
        c["cross_k"] = jnp.zeros((batch, cfg.enc_seq, KV * hd), dtype)
        c["cross_v"] = jnp.zeros((batch, cfg.enc_seq, KV * hd), dtype)
        return {"b0": c}

    return [jax.vmap(one)(jnp.arange(cfg.n_layers))]


def decode_step(cfg, p: Params, caches: List[Any], token: jnp.ndarray,
                pos: jnp.ndarray):
    pe = jax.lax.dynamic_slice_in_dim(p["pos_dec"], pos.astype(jnp.int32),
                                      1, axis=0)            # (1, d)
    x = jnp.take(p["embed"], token, axis=0) + pe[None]       # (B, 1, d)
    x = with_logical_constraint(x, "batch", None, None)
    x, ys, _ = _scan_stack(cfg, p["dec_stack"], x, ("decoder",),
                           caches=caches[0], cache_pos=pos)
    x = apply_norm(cfg, x, p["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, p["lm_head"])
    return logits, [ys]


def prefill(cfg, p: Params, batch: Dict[str, jnp.ndarray]):
    logits, caches, _ = forward(cfg, p, batch, collect_cache=True)
    return logits[:, -1:, :], caches
