"""Mamba-2 SSD (state-space duality) layer — chunked scan, pure JAX.

Faithful to the SSD block of arXiv:2405.21060 with one shard-friendly
restructuring: the fused ``in_proj`` is split into separate projections
(z, x, B, C, dt) so the head-parallel parts (z, x, dt) can be tensor-sharded
over the ``model`` axis while the group-shared B/C stay replicated
(n_groups=1 in the assigned configs).  The short causal conv is likewise
split into an x-conv (sharded channels) and a BC-conv (replicated).

The chunked algorithm runs as a `lax.scan` over sequence chunks so the
intra-chunk (q x q) decay matrices never materialize for the whole sequence
— per-step memory is O(chunk^2), total work O(S*chunk + S*N*P), the same
blocking a TPU kernel wants (see kernels/ssd_scan.py).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import Params, dense_init, get_scan_unroll, rmsnorm


def ssd_init(cfg, key, dtype) -> Tuple[Params, Dict]:
    d = cfg.d_model
    di = cfg.d_inner
    H = cfg.ssm_heads
    N = cfg.ssm_state
    G = 1  # n_groups
    ks = jax.random.split(key, 8)
    p = {
        "w_z": dense_init(ks[0], (d, di), dtype),
        "w_x": dense_init(ks[1], (d, di), dtype),
        "w_B": dense_init(ks[2], (d, G * N), dtype),
        "w_C": dense_init(ks[3], (d, G * N), dtype),
        "w_dt": dense_init(ks[4], (d, H), dtype),
        "conv_x": (jax.random.normal(ks[5], (cfg.ssm_conv_width, di),
                                     jnp.float32) * 0.1).astype(dtype),
        "conv_BC": (jax.random.normal(ks[6], (cfg.ssm_conv_width, 2 * G * N),
                                      jnp.float32) * 0.1).astype(dtype),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "w_out": dense_init(ks[7], (di, d), dtype, in_axis=0),
    }
    ax = {
        "w_z": ("embed", "heads"), "w_x": ("embed", "heads"),
        "w_B": ("embed", None), "w_C": ("embed", None),
        "w_dt": ("embed", "heads"),
        "conv_x": (None, "heads"), "conv_BC": (None, None),
        "A_log": ("heads",), "D": ("heads",), "dt_bias": ("heads",),
        "norm": ("heads",),
        "w_out": ("heads", "embed"),
    }
    # conv biases, zero as Mamba-2's are (published checkpoints train them)
    p["conv_x_bias"] = jnp.zeros((di,), dtype)
    p["conv_BC_bias"] = jnp.zeros((2 * G * N,), dtype)
    ax["conv_x_bias"], ax["conv_BC_bias"] = ("heads",), (None,)
    return p, ax


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray,
                 b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv along seq, then SiLU: x (B,S,C), w (K,C),
    bias b (C,)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.broadcast_to(b.astype(jnp.float32), x.shape)
    for i in range(K):
        out = out + xp[:, i:i + x.shape[1], :].astype(jnp.float32) * \
            w[i].astype(jnp.float32)
    return jax.nn.silu(out).astype(x.dtype)


def _segsum(dA: jnp.ndarray) -> jnp.ndarray:
    """dA: (B,q,H) -> (B,H,q,q) with out[...,i,j] = sum_{j<k<=i} dA_k
    (lower-triangular), -inf above the diagonal."""
    q = dA.shape[1]
    x = jnp.swapaxes(dA, 1, 2)                       # (B,H,q)
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]       # i,j -> cs_i - cs_j
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_scan_ref(x, dt, A, B, C, chunk: int,
                 init_state: Optional[jnp.ndarray] = None,
                 return_state: bool = False):
    """Chunked SSD: x (B,S,H,P), dt (B,S,H), A (H), B/C (B,S,G,N).

    Returns y (B,S,H,P) [and final state (B,H,P,N)].
    This is also the oracle for kernels/ssd_scan.py.
    """
    Bsz, S, H, P = x.shape
    G = B.shape[2]
    N = B.shape[3]
    hpg = H // G
    q = chunk
    while S % q:
        q -= 1
    nc = S // q

    xf = (x * dt[..., None]).astype(jnp.float32)     # fold dt into x
    xc = xf.reshape(Bsz, nc, q, H, P)
    dtc = dt.reshape(Bsz, nc, q, H)
    Bc = B.astype(jnp.float32).reshape(Bsz, nc, q, G, N)
    Cc = C.astype(jnp.float32).reshape(Bsz, nc, q, G, N)

    state0 = (jnp.zeros((Bsz, H, P, N), jnp.float32) if init_state is None
              else init_state.astype(jnp.float32))

    def step(state, inp):
        xb, dtb, Bb, Cb = inp                        # (B,q,H,P) etc.
        dA = dtb * A                                  # (B,q,H)
        cum = jnp.cumsum(dA, axis=1)                  # (B,q,H)
        L = jnp.exp(_segsum(dA))                      # (B,H,q,q)
        Lg = L.reshape(Bsz, G, hpg, q, q)
        xg = xb.reshape(Bsz, q, G, hpg, P)
        # intra-chunk
        scores = jnp.einsum("bqgn,bsgn->bgqs", Cb, Bb)          # (B,G,q,q)
        y_diag = jnp.einsum("bgqs,bghqs,bsghp->bqghp", scores, Lg, xg)
        # inter-chunk: contribution of the incoming state
        dec = jnp.exp(cum).reshape(Bsz, q, G, hpg)               # (B,q,G,hpg)
        stg = state.reshape(Bsz, G, hpg, P, N)
        y_off = jnp.einsum("bqgn,bghpn,bqgh->bqghp", Cb, stg, dec)
        y = (y_diag + y_off).reshape(Bsz, q, H, P)
        # new chunk state
        dec_st = jnp.exp(cum[:, -1:, :] - cum)                   # (B,q,H)
        contrib = jnp.einsum("bsgn,bsghp->bghpn",
                             Bb, (xb * dec_st[..., None]).reshape(
                                 Bsz, q, G, hpg, P))
        chunk_decay = jnp.exp(cum[:, -1, :])                     # (B,H)
        state_new = state * chunk_decay[..., None, None] + \
            contrib.reshape(Bsz, H, P, N)
        return state_new, y

    inputs = (jnp.swapaxes(xc, 0, 1), jnp.swapaxes(dtc, 0, 1),
              jnp.swapaxes(Bc, 0, 1), jnp.swapaxes(Cc, 0, 1))
    state, ys = jax.lax.scan(jax.checkpoint(step), state0, inputs,
                             unroll=True if get_scan_unroll() else 1)
    y = jnp.swapaxes(ys, 0, 1).reshape(Bsz, S, H, P)
    if return_state:
        return y.astype(x.dtype), state
    return y.astype(x.dtype)


def ssd_forward(cfg, p: Params, x: jnp.ndarray, *,
                init_state: Optional[jnp.ndarray] = None,
                return_state: bool = False):
    """Full SSD block: project -> conv -> SSD scan -> gate -> out-proj.

    x: (B,S,d) -> (B,S,d).  With ``return_state`` also returns the decode
    cache dict (final SSM state + conv tails) so prefill can seed decoding.
    """
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    K = cfg.ssm_conv_width
    z = jnp.einsum("bsd,dh->bsh", x, p["w_z"])
    xin_pre = jnp.einsum("bsd,dh->bsh", x, p["w_x"])
    BC_pre = jnp.einsum("bsd,dh->bsh", x,
                        jnp.concatenate([p["w_B"], p["w_C"]], axis=1))
    dt = jnp.einsum("bsd,dh->bsh", x, p["w_dt"])

    xin = _causal_conv(xin_pre, p["conv_x"], p["conv_x_bias"])
    BC = _causal_conv(BC_pre, p["conv_BC"], p["conv_BC_bias"])
    Bm, Cm = jnp.split(BC, 2, axis=-1)

    Bsz, S = x.shape[0], x.shape[1]
    A = -jnp.exp(p["A_log"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    xh = xin.reshape(Bsz, S, H, P)
    Bm = Bm.reshape(Bsz, S, 1, N)
    Cm = Cm.reshape(Bsz, S, 1, N)

    out = ssd_scan_ref(xh, dt, A, Bm, Cm, cfg.ssm_chunk,
                       init_state=init_state, return_state=return_state)
    y, state = out if return_state else (out, None)
    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(Bsz, S, H * P).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    y = rmsnorm(y, p["norm"], cfg.rms_eps)
    y = jnp.einsum("bsh,hd->bsd", y, p["w_out"])
    if return_state:
        cache = {"state": state,
                 "conv_x": xin_pre[:, S - (K - 1):, :],
                 "conv_BC": BC_pre[:, S - (K - 1):, :]}
        return y, cache
    return y


# ---------------------------------------------------------------------------
# decode: recurrent single-token step
# ---------------------------------------------------------------------------

def init_ssd_cache(cfg, batch: int, dtype) -> Dict[str, jnp.ndarray]:
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    K = cfg.ssm_conv_width
    di = cfg.d_inner
    return {
        "state": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv_x": jnp.zeros((batch, K - 1, di), dtype),
        "conv_BC": jnp.zeros((batch, K - 1, 2 * N), dtype),
    }


def _conv_step(buf: jnp.ndarray, xt: jnp.ndarray, w: jnp.ndarray,
               b: jnp.ndarray):
    """buf (B,K-1,C) holds previous inputs; xt (B,C); bias b (C,).
    Returns (y, new_buf)."""
    full = jnp.concatenate([buf, xt[:, None, :]], axis=1)   # (B,K,C)
    y = jnp.einsum("bkc,kc->bc", full.astype(jnp.float32),
                   w.astype(jnp.float32)) + b.astype(jnp.float32)
    return jax.nn.silu(y).astype(xt.dtype), full[:, 1:, :]


def ssd_decode_step(cfg, p: Params, x: jnp.ndarray, cache: Dict):
    """x: (B,1,d) -> (y (B,1,d), new_cache)."""
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    xt = x[:, 0, :]
    z = xt @ p["w_z"]
    xin = xt @ p["w_x"]
    BC = xt @ jnp.concatenate([p["w_B"], p["w_C"]], axis=1)
    dt = xt @ p["w_dt"]

    xin, conv_x = _conv_step(cache["conv_x"], xin, p["conv_x"],
                             p["conv_x_bias"])
    BC, conv_BC = _conv_step(cache["conv_BC"], BC, p["conv_BC"],
                             p["conv_BC_bias"])
    Bm, Cm = jnp.split(BC, 2, axis=-1)                       # (B,N) each

    A = -jnp.exp(p["A_log"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    dA = jnp.exp(dt * A)                                      # (B,H)
    xh = xin.reshape(-1, H, P).astype(jnp.float32)
    state = cache["state"] * dA[..., None, None] + \
        jnp.einsum("bn,bhp,bh->bhpn", Bm.astype(jnp.float32), xh, dt)
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), state)
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(-1, H * P).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    y = rmsnorm(y, p["norm"], cfg.rms_eps)
    y = (y @ p["w_out"])[:, None, :]
    return y, {"state": state, "conv_x": conv_x, "conv_BC": conv_BC}
