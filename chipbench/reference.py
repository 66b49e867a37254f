"""Plain float32 reference of a Qwen2-style decoder, and its fp8 control.

It imports nothing of the program and takes nothing the program made.  Its
weights come from the seed: it draws the matrices as the program's seeded
initializer does (the same key splits, truncated normals scaled by
1/sqrt(fan_in), embeddings N(0, 0.02) over the vocabulary padded to a
multiple of 256), stored in the configuration's dtype and widened to
float32 here.  The leaves that initializer leaves at 0 and 1, the q/k/v
biases and the norm scales, are drawn by :func:`norms_and_biases` from the
seed instead; the harness puts the same draws into the program's weights,
so a program that drops a bias or a norm's scale fails ``correct``.  A
program whose initializer draws other numbers from the seed is told apart
by ``correct`` too.

The forward is the published Qwen2 block in float32 at "highest" matmul
precision: RMSNorm, q/k/v with bias, rotary embeddings (rotate-half),
grouped-query causal attention, SwiGLU, final RMSNorm, LM head.  It runs
layer by layer, each layer's weights drawn when needed, and attention in
blocks of query rows, so that a 7B model fits one chip.

``served_gaps`` reads, for each served token, how far its logit lies below
the reference's best at that position.  With ``control=True`` it also
reads the gap of the token that the control, the same forward with every
projection's operands rounded to float8_e4m3fn under a per-tensor scale,
puts first.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
HEAD_ROWS = 64          # LM-head positions per block
BIAS_STD = 0.5          # q/k/v biases: about half a unit-variance projection
SCALE_STD = 0.2         # norm scales: 1 + N(0, 0.2)
EXTRA_STREAM = 0xB1A5   # folded into the seed's key for those draws
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def padded_vocab(vocab: int) -> int:
    return (vocab + 255) // 256 * 256


def _dims(model: dict) -> dict:
    d, h = model["hidden_size"], model["num_attention_heads"]
    return dict(d=d, H=h, KV=model["num_key_value_heads"],
                hd=model.get("head_dim", d // h),
                ff=model["intermediate_size"], L=model["num_hidden_layers"],
                V=model["vocab_size"], Vp=padded_vocab(model["vocab_size"]),
                tied=bool(model["tie_word_embeddings"]),
                eps=float(model["rms_norm_eps"]),
                theta=float(model["rope_theta"]),
                dtype=jnp.dtype(model["torch_dtype"]))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _dense(key, shape, fan_in, dtype):
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def _embed(key, m):
    return (jax.random.normal(key, (m["Vp"], m["d"]), jnp.float32)
            * 0.02).astype(m["dtype"])


def _top_keys(seed: int):
    """(embedding key, LM-head key, key of the layer stack)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return ks[0], ks[1], ks[2]


@partial(jax.jit, static_argnums=(1,))
def _layer_weights(layer_key, mt):
    m = dict(mt)
    d, H, KV, hd, ff, dt = m["d"], m["H"], m["KV"], m["hd"], m["ff"], m["dtype"]
    block = jax.random.split(jax.random.split(layer_key, 1)[0], 8)
    ka = jax.random.split(block[0], 4)
    km = jax.random.split(block[3], 3)
    return {"wq": _dense(ka[0], (d, H * hd), d, dt),
            "wk": _dense(ka[1], (d, KV * hd), d, dt),
            "wv": _dense(ka[2], (d, KV * hd), d, dt),
            "wo": _dense(ka[3], (H * hd, d), H * hd, dt),
            "wg": _dense(km[0], (d, ff), d, dt),
            "wu": _dense(km[1], (d, ff), d, dt),
            "wd": _dense(km[2], (ff, d), ff, dt)}


@partial(jax.jit, static_argnums=(1,))
def _norms_and_biases(key, mt):
    m = dict(mt)
    L, d, q, kv, dt = m["L"], m["d"], m["H"] * m["hd"], m["KV"] * m["hd"], \
        m["dtype"]
    ks = jax.random.split(key, 6)

    def bias(k, n):
        return (BIAS_STD * jax.random.normal(k, (L, n))).astype(dt)

    def scale(k, shape):
        return (1.0 + SCALE_STD * jax.random.normal(k, shape)).astype(dt)
    return {"bq": bias(ks[0], q), "bk": bias(ks[1], kv),
            "bv": bias(ks[2], kv), "ln1": scale(ks[3], (L, d)),
            "ln2": scale(ks[4], (L, d)), "final": scale(ks[5], (d,))}


def norms_and_biases(model: dict, seed: int) -> dict:
    """Per layer (leading axis): q/k/v biases ``bq``, ``bk``, ``bv`` drawn
    N(0, BIAS_STD), norm scales ``ln1`` (before attention) and ``ln2``
    (before the MLP) drawn 1 + N(0, SCALE_STD); ``final``, the final norm's
    scale, likewise.  In the configuration's dtype, on the default
    device."""
    m = _dims(model)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), EXTRA_STREAM)
    return _norms_and_biases(key, tuple(sorted(m.items())))


@partial(jax.jit, static_argnums=(2,))
def _head_weights(embed_key, head_key, mt):
    """(embedding table, LM head as (d, V)) over the real vocabulary."""
    m = dict(mt)
    emb = _embed(embed_key, m)
    head = emb if m["tied"] else _embed(head_key, m)
    return emb[: m["V"]], head[: m["V"]].T


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _q8(a):
    """Round to float8_e4m3fn under a per-tensor scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(F8).astype(jnp.float32) * s


def _mm(a, w, low: bool):
    w = w.astype(jnp.float32)
    if low:
        a, w = _q8(a), _q8(w)
    return a @ w


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x (B, S, n, hd); rotate-half rotary embedding at positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, mt, low):
    m = dict(mt)
    B, S, _ = x.shape
    H, KV, hd = m["H"], m["KV"], m["hd"]
    G = H // KV
    pos = jnp.arange(S)
    h = _rms(x, m["eps"]) * w["ln1"].astype(jnp.float32)
    q = (_mm(h, w["wq"], low) + w["bq"].astype(jnp.float32)
         ).reshape(B, S, H, hd)
    k = (_mm(h, w["wk"], low) + w["bk"].astype(jnp.float32)
         ).reshape(B, S, KV, hd)
    v = (_mm(h, w["wv"], low) + w["bv"].astype(jnp.float32)
         ).reshape(B, S, KV, hd)
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    k = jnp.repeat(k, G, axis=2)              # head j*G+g reads kv head j
    v = jnp.repeat(v, G, axis=2)
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[:, s0:s0 + Q_BLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(hd)
        qpos = pos[s0:s0 + Q_BLOCK]
        sc = jnp.where(qpos[:, None] >= pos[None, :], sc, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v))
    att = jnp.concatenate(outs, 1).reshape(B, S, H * hd)
    x = x + _mm(att, w["wo"], low)
    h = _rms(x, m["eps"]) * w["ln2"].astype(jnp.float32)
    g = _mm(h, w["wg"], low)
    u = _mm(h, w["wu"], low)
    return x + _mm(jax.nn.silu(g) * u, w["wd"], low)


@partial(jax.jit, static_argnums=(5,))
def _head_gaps(x, head, scale, served, valid, mt):
    """Per position: reference best minus the served token's logit, and
    minus the logit of the control's first choice (``x`` holds the two
    forwards' final hidden states)."""
    m = dict(mt)
    scale = scale.astype(jnp.float32)
    xr = _rms(x[0], m["eps"]) * scale
    ref = xr @ head.astype(jnp.float32)                     # (B, R, V)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    xc = _rms(x[1], m["eps"]) * scale
    ctrl = _mm(xc, head, True).argmax(-1)
    cgap = best - jnp.take_along_axis(ref, ctrl[..., None], -1)[..., 0]
    return jnp.where(valid, gap, 0.0), jnp.where(valid, cgap, 0.0)


def served_gaps(model: dict, seed: int, prompts, outs, out_max: int,
                control: bool = False) -> dict:
    """Teacher-force each prompt with its served tokens.

    ``prompts``: equal-length int arrays; ``outs``: the served token lists.
    Returns flat arrays over every served token: ``gap`` (reference best
    minus the served token's logit) and, with ``control``, ``control_gap``.
    """
    m = _dims(model)
    mt = tuple(sorted(m.items()))
    S = len(prompts[0])
    L = S + out_max - 1
    B = len(prompts)
    toks = np.zeros((B, L), np.int32)
    served = np.zeros((B, out_max), np.int32)
    valid = np.zeros((B, out_max), bool)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seq = np.concatenate([p, o[:-1]]).astype(np.int32)
        toks[i, : len(seq)] = seq
        served[i, : len(o)] = o
        valid[i, : len(o)] = True
    ek, hk, sk = _top_keys(seed)
    layer_keys = jax.random.split(sk, m["L"])
    extra = norms_and_biases(model, seed)
    with jax.default_matmul_precision("highest"):
        emb, head = _head_weights(ek, hk, mt)
        x = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
        del emb
        xs = [x, x] if control else [x]
        for li in range(m["L"]):
            w = _layer_weights(layer_keys[li], mt)
            w.update({k: extra[k][li] for k in
                      ("bq", "bk", "bv", "ln1", "ln2")})
            xs = [_layer(xs[0], w, mt, False)] + (
                [_layer(xs[1], w, mt, True)] if control else [])
            del w
        last = [xx[:, S - 1:] for xx in xs]
        if not control:
            last = [last[0], last[0]]
        gaps, cgaps = [], []
        for r0 in range(0, out_max, HEAD_ROWS):
            sl = slice(r0, r0 + HEAD_ROWS)
            g, c = _head_gaps(jnp.stack([last[0][:, sl], last[1][:, sl]]),
                              head, extra["final"],
                              jnp.asarray(served[:, sl]),
                              jnp.asarray(valid[:, sl]), mt)
            gaps.append(np.asarray(g))
            cgaps.append(np.asarray(c))
    out = {"gap": np.concatenate(gaps, 1)[valid]}
    if control:
        out["control_gap"] = np.concatenate(cgaps, 1)[valid]
    return out
