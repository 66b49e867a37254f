"""Attention: chunked flash-style forward, GQA, sliding-window, cross,
and split-KV decode — pure JAX (the Pallas kernels in ``repro/kernels`` are
versions of the same math, off the model path; the model uses these jnp
paths).

Sharding strategy (see DESIGN.md):
  * projections — TP over the *fused* head dim (H*hd).  Head counts of the
    assigned archs rarely divide the 16-way model axis, but H*hd always does
    (hd is 64/128), so column/row parallelism is universally legal.
  * attention core (train/prefill) — query-sequence sharding over ``model``
    inside a shard_map: each shard ropes its local q/k at absolute
    positions, all-gathers K/V, and runs the chunked online-softmax locally.
    Works for any head count; attention FLOPs split 16-ways.
  * decode — split-KV: the cache's sequence dim is sharded over ``model``;
    partial softmax statistics combine exactly through jnp reductions, which
    GSPMD lowers to the matching collectives.  Per-device cache bytes drop
    by the model-axis size.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import Params, apply_rope, dense_init, get_mesh_context, rmsnorm

NEG_INF = -1e30


def softmax_scale(cfg, hd: int) -> float:
    """The config's attention multiplier, else 1/sqrt(head_dim)."""
    m = cfg.attention_multiplier
    return 1.0 / math.sqrt(hd) if m is None else m


def pick_chunk(s: int, target: int) -> int:
    """Largest divisor of ``s`` that is <= target (>=1)."""
    c = min(target, s)
    while s % c:
        c -= 1
    return max(c, 1)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attention_init(cfg, key, dtype, *, cross: bool = False
                   ) -> Tuple[Params, Dict]:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dtype),
        "wk": dense_init(ks[1], (d, KV * hd), dtype),
        "wv": dense_init(ks[2], (d, KV * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype, in_axis=0),
    }
    ax = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
        ax["bq"] = ("heads",)
        ax["bk"] = ("kv_heads",)
        ax["bv"] = ("kv_heads",)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
        ax["q_norm"] = (None,)
        ax["k_norm"] = (None,)
    return p, ax


def _project_qkv(cfg, p: Params, x: jnp.ndarray,
                 kv_x: Optional[jnp.ndarray] = None):
    """Returns q (B,Sq,H,hd), k/v (B,Skv,KV,hd) — un-roped."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", src, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", src, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(*q.shape[:-1], H, hd)
    k = k.reshape(*k.shape[:-1], KV, hd)
    v = v.reshape(*v.shape[:-1], KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rms_eps)
    return q, k, v


# ---------------------------------------------------------------------------
# chunked flash-style attention (local math)
# ---------------------------------------------------------------------------

def _block_attn(q, k, v, mask, scale):
    """One (q-block, kv-block) tile of online-softmax attention.

    q: (B,cq,KV,G,hd)  k/v: (B,ck,KV,hd)  mask: (cq,ck) bool (True = keep)
    Returns fp32 (max, exp-sum, acc) for this block.
    """
    s = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale         # (B,KV,G,cq,ck)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                               # (B,KV,G,cq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgqs,bskh->bkgqh", p, v.astype(jnp.float32))
    return m, l, acc


def chunked_attention(cfg, q, k, v, q_positions, kv_positions, *,
                      causal: bool, window: int = 0,
                      q_chunk: int = 512, kv_chunk: int = 1024) -> jnp.ndarray:
    """Flash-style attention with online softmax over KV chunks.

    q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd).  Positions are absolute 1-D arrays.
    window>0 = sliding-window: banded gather, O(Sq*(window+chunk)) compute.
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = softmax_scale(cfg, hd)
    cq = pick_chunk(Sq, q_chunk)
    qg = q.reshape(B, Sq, KV, G, hd)

    if window and window > 0:
        out = _banded_attention(qg, k, v, q_positions, kv_positions,
                                window=window, cq=cq, scale=scale)
        return out.reshape(B, Sq, H, hd).astype(q.dtype)

    ck = pick_chunk(Skv, kv_chunk)
    n_q, n_k = Sq // cq, Skv // ck

    def per_q_block(qi):
        qb = jax.lax.dynamic_slice_in_dim(qg, qi * cq, cq, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_positions, qi * cq, cq, axis=0)

        def kv_step(carry, ki):
            m_run, l_run, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, ki * ck, ck, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, ki * ck, ck, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(kv_positions, ki * ck, ck, axis=0)
            mask = (qp[:, None] >= kp[None, :]) if causal else \
                jnp.ones((cq, ck), bool)
            m, l, a = _block_attn(qb, kb, vb, mask, scale)
            m_new = jnp.maximum(m_run, m)
            r_old = jnp.exp(m_run - m_new)
            r_blk = jnp.exp(m - m_new)
            l_new = l_run * r_old + l * r_blk
            acc_new = acc * r_old[..., None] + a * r_blk[..., None]
            return (m_new, l_new, acc_new), None

        init = (jnp.full((B, KV, G, cq), NEG_INF, jnp.float32),
                jnp.zeros((B, KV, G, cq), jnp.float32),
                jnp.zeros((B, KV, G, cq, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step), init, jnp.arange(n_k))
        return acc / jnp.maximum(l, 1e-30)[..., None]      # (B,KV,G,cq,hd)

    _, outs = jax.lax.scan(lambda c, qi: (c, per_q_block(qi)), 0,
                           jnp.arange(n_q))
    out = jnp.moveaxis(outs, 0, 3)                          # (B,KV,G,n_q,cq,hd)
    out = out.reshape(B, KV, G, Sq, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _banded_attention(qg, k, v, q_positions, kv_positions, *,
                      window: int, cq: int, scale: float) -> jnp.ndarray:
    """Sliding-window attention: each q chunk attends a fixed-size KV band
    ``[chunk_start - window, chunk_end)`` — linear in sequence length.

    Assumes positions are contiguous and aligned between q and kv (the
    self-attention case; SWA cross-attention is not a thing we need).
    """
    B, Sq, KV, G, hd = qg.shape
    band = window + cq
    n_q = Sq // cq
    pad = window
    kpad = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    vpad = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    kp_pad = jnp.pad(kv_positions, (pad, 0), constant_values=-1)

    def per_q_block(qi):
        qb = jax.lax.dynamic_slice_in_dim(qg, qi * cq, cq, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_positions, qi * cq, cq, axis=0)
        # band = [g0 - window, g0 + cq) in *global* kv coords, where g0 is the
        # chunk's absolute start (q may be a sequence shard); kpad's front
        # padding of `window` makes the padded slice start exactly g0.
        start = qp[0]
        kb = jax.lax.dynamic_slice_in_dim(kpad, start, band, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vpad, start, band, axis=1)
        kp = jax.lax.dynamic_slice_in_dim(kp_pad, start, band, axis=0)
        mask = (qp[:, None] >= kp[None, :]) & \
               (qp[:, None] - kp[None, :] < window) & (kp[None, :] >= 0)
        m, l, a = _block_attn(qb, kb, vb, mask, scale)
        return a / jnp.maximum(l, 1e-30)[..., None]

    _, outs = jax.lax.scan(
        lambda c, qi: (c, jax.checkpoint(per_q_block)(qi)), 0,
        jnp.arange(n_q))
    out = jnp.moveaxis(outs, 0, 3).reshape(B, KV, G, Sq, hd)
    return out.transpose(0, 3, 1, 2, 4)


def _flash_full(cfg, q, k, v, *, causal, window, use_rope, base_pos: int = 0):
    """Rope + chunked attention.  Under a mesh whose model axis has M > 1
    devices, the core is sharded by what the shapes allow, first match:

      seq        — M divides S: query-sequence shards + KV all-gather
                   (legal for any head count).
      heads      — M divides H and KV: each shard attends its own heads
                   over the full sequence, with no collective.
      replicated — neither: the model axis's devices repeat the core.

    q/k/v are un-roped projections, (B,S,*,hd).  Returns (y, k_roped, v).
    """
    mesh, data_spec, model_axis = get_mesh_context()
    B, S = q.shape[0], q.shape[1]

    def local(q_l, k_l, v_l, shard_idx, n_shards):
        Sl = q_l.shape[1]
        qpos = base_pos + shard_idx * Sl + jnp.arange(Sl)
        if use_rope:
            q_r = apply_rope(q_l, qpos, cfg.rope_theta)
            k_r = apply_rope(k_l, qpos, cfg.rope_theta)
        else:
            q_r, k_r = q_l, k_l
        if n_shards > 1:
            k_full = jax.lax.all_gather(k_r, model_axis, axis=1, tiled=True)
            v_full = jax.lax.all_gather(v_l, model_axis, axis=1, tiled=True)
        else:
            k_full, v_full = k_r, v_l
        kpos = base_pos + jnp.arange(k_full.shape[1])
        y = chunked_attention(cfg, q_r, k_full, v_full, qpos, kpos,
                              causal=causal, window=window)
        return y, k_r, v_l

    M = mesh.shape[model_axis] if mesh is not None and \
        model_axis in mesh.axis_names else 1
    if M > 1 and S % M == 0:
        dp = P(data_spec, model_axis, None, None)

        def body(q_l, k_l, v_l):
            i = jax.lax.axis_index(model_axis)
            return local(q_l, k_l, v_l, i, M)

        return jax.shard_map(body, mesh=mesh, in_specs=(dp, dp, dp),
                             out_specs=(dp, dp, dp),
                             check_vma=False)(q, k, v)
    if M > 1 and cfg.n_heads % M == 0 and cfg.n_kv_heads % M == 0:
        dq = P(data_spec, None, model_axis, None)

        def body_h(q_l, k_l, v_l):
            qpos = base_pos + jnp.arange(S)
            if use_rope:
                q_r = apply_rope(q_l, qpos, cfg.rope_theta)
                k_r = apply_rope(k_l, qpos, cfg.rope_theta)
            else:
                q_r, k_r = q_l, k_l
            y = chunked_attention(cfg, q_r, k_r, v_l, qpos, qpos,
                                  causal=causal, window=window)
            return y, k_r, v_l

        return jax.shard_map(body_h, mesh=mesh, in_specs=(dq, dq, dq),
                             out_specs=(dq, dq, dq), check_vma=False
                             )(q, k, v)
    return local(q, k, v, 0, 1)


# ---------------------------------------------------------------------------
# decode (split-KV) + cache plumbing
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, max_seq: int, dtype) -> Dict[str, jnp.ndarray]:
    """(B,S,KV*hd) per leaf, the KV heads fused into one minor dim so that a
    position's row is contiguous.  Sliding-window archs keep only a ring
    buffer of ``window`` entries."""
    KV, hd = cfg.n_kv_heads, cfg.head_dim_
    S = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    return {
        "k": jnp.zeros((batch, S, KV * hd), dtype),
        "v": jnp.zeros((batch, S, KV * hd), dtype),
    }


def fuse_heads(x: jnp.ndarray) -> jnp.ndarray:
    """(...,KV,hd) -> (...,KV*hd), the layout of the decode cache."""
    return x.reshape(*x.shape[:-2], -1)


def cache_slot(cfg, S: int, pos: jnp.ndarray) -> jnp.ndarray:
    """The cache row of position ``pos`` (ring-indexed under sliding
    window)."""
    return pos % S if cfg.sliding_window else pos


def with_new_row(cfg, cache: Dict[str, jnp.ndarray], k_new, v_new,
                 pos: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """One layer's K/V (B,S,KV*hd) as decode attends them: the new token's
    row at its slot, the cache's rows elsewhere.  The select fuses into
    attention's read of the slab and is never stored; ``update_cache``
    writes the row.  k_new/v_new: (B,1,KV*hd) in the cache's dtype."""
    S = cache["k"].shape[1]
    hit = (jnp.arange(S) == cache_slot(cfg, S, pos))[None, :, None]
    return {"k": jnp.where(hit, k_new, cache["k"]),
            "v": jnp.where(hit, v_new, cache["v"])}


def update_cache(cfg, cache: Dict[str, jnp.ndarray],
                 rows: Dict[str, jnp.ndarray], pos: jnp.ndarray
                 ) -> Dict[str, jnp.ndarray]:
    """Write every layer's new K/V row (L,B,1,KV*hd) into the stacked cache
    (L,B,S,KV*hd) at ``pos``'s slot.

    One ``dynamic_update_slice`` per leaf, in place when the cache is
    donated: nothing else of the slab is read or written.  A split-KV
    (sequence-sharded) cache takes the same write; GSPMD lowers it to an
    in-place row write on each shard (compiled for a described v5e 2x2).
    """
    return {n: jax.lax.dynamic_update_slice(
                cache[n], r.astype(cache[n].dtype),
                (0, 0, cache_slot(cfg, cache[n].shape[2], pos), 0))
            for n, r in rows.items()}


def decode_attention(cfg, q, cache: Dict[str, jnp.ndarray],
                     pos: jnp.ndarray) -> jnp.ndarray:
    """Single-token attention over the (possibly seq-sharded) cache.

    q: (B,1,H,hd), cache k/v: (B,S,KV*hd) -> (B,1,H,hd).  Each query head
    is laid into its KV head's lanes of a KV*hd row, zeros elsewhere, so
    one matmul over the fused lanes scores every head against its own K
    and the cache is read in its own layout.  Exact softmax even when the
    cache's seq dim is sharded: the reductions lower to psum-style
    collectives under GSPMD.
    """
    B, _, H, hd = q.shape
    k, v = cache["k"], cache["v"]
    S, KV = k.shape[1], k.shape[2] // hd
    G = H // KV
    scale = softmax_scale(cfg, hd)
    own = jnp.eye(KV, dtype=jnp.float32)
    qg = jnp.einsum("bkgh,kl->bkglh", q.reshape(B, KV, G, hd).astype(
        jnp.float32), own).reshape(B, KV, G, KV * hd)
    s = jnp.einsum("bkgc,bsc->bkgs", qg,
                   k.astype(jnp.float32)) * scale          # (B,KV,G,S)
    iota = jnp.arange(S)
    if cfg.sliding_window:
        # ring slot i holds absolute position p_i = i + floor((pos-i)/S)*S
        wrap = (pos - iota) // S
        abs_pos = iota + wrap * S
        valid = (abs_pos >= 0) & (abs_pos <= pos) & \
                (pos - abs_pos < cfg.sliding_window)
    else:
        valid = iota <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bsc->bkgc", p / jnp.maximum(l, 1e-30),
                     v.astype(jnp.float32)).reshape(B, KV, G, KV, hd)
    out = jnp.einsum("bkglh,kl->bkgh", out, own)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# full module: project -> rope -> attend -> out-proj
# ---------------------------------------------------------------------------

def attention_forward(cfg, p: Params, x: jnp.ndarray, *,
                      causal: bool = True, window: int = 0,
                      use_rope: bool = True,
                      kv_x: Optional[jnp.ndarray] = None,
                      cache: Optional[Dict] = None,
                      cache_pos: Optional[jnp.ndarray] = None,
                      precomputed_kv: Optional[Tuple] = None):
    """Unified attention module.

    * train/prefill (cache=None): chunked flash attention; returns
      (y, (k_roped, v)) so prefill can seed the decode cache.
    * decode (cache given, x is (B,1,d)): split-KV decode over the layer's
      cache with the new token's K/V row selected in; returns (y, row), the
      row for ``update_cache`` to write.
    * cross-attention decode: pass precomputed_kv=(k, v), the encoder's
      K/V (B,S_enc,KV*hd) from the decode cache; decode just reads them.
    """
    H, hd = cfg.n_heads, cfg.head_dim_

    if precomputed_kv is not None:
        q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(
            *x.shape[:-1], H, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k, v = precomputed_kv
        y = decode_attention(cfg, q, {"k": k, "v": v},
                             jnp.asarray(k.shape[1] - 1))
        y = jnp.einsum("bsh,hd->bsd", y.reshape(*y.shape[:-2], H * hd),
                       p["wo"])
        return y, None

    q, k, v = _project_qkv(cfg, p, x, kv_x)

    if kv_x is not None and cache is None:
        # cross-attention, full mode (whisper decoder): no rope, not causal,
        # q/kv lengths differ -> direct chunked attention
        qpos = jnp.arange(x.shape[1])
        kpos = jnp.arange(kv_x.shape[1])
        y = chunked_attention(cfg, q, k, v, qpos, kpos, causal=False)
        y = jnp.einsum("bsh,hd->bsd", y.reshape(*y.shape[:-2], H * hd),
                       p["wo"])
        return y, (k, v)

    if cache is not None:
        if use_rope:
            q = apply_rope(q, cache_pos[None], cfg.rope_theta)
            k = apply_rope(k, cache_pos[None], cfg.rope_theta)
        row = {"k": fuse_heads(k).astype(cache["k"].dtype),
               "v": fuse_heads(v).astype(cache["v"].dtype)}
        y = decode_attention(cfg, q, with_new_row(cfg, cache, row["k"],
                                                  row["v"], cache_pos),
                             cache_pos)
        y = jnp.einsum("bsh,hd->bsd", y.reshape(*y.shape[:-2], H * hd),
                       p["wo"])
        return y, row

    y, k_r, v_r = _flash_full(cfg, q, k, v, causal=causal, window=window,
                              use_rope=use_rope)
    y = jnp.einsum("bsh,hd->bsd", y.reshape(*y.shape[:-2], H * hd), p["wo"])
    return y, (k_r, v_r)
