"""Plain float32 reference of Granite-4.0-H (Hugging Face ``model_type``
``granitemoehybrid``, no experts), its fp8 control, and its work counts.

It imports nothing of the program and takes nothing the program made.  Its
weights come from the seed: it draws the matrices as the program's seeded
initializer does (the same key splits over the same layer stacks: the layer
kinds' shortest period scanned as one stack where it repeats, else one
stack per run of one kind; truncated normals scaled by 1/sqrt(fan_in), the
conv taps N(0, 0.1), embeddings N(0, 0.02) over the vocabulary padded to a
multiple of 256), stored in the configuration's dtype and widened to
float32 one layer at a time.  The leaves that initializer leaves constant
are drawn by :func:`extra_leaves` from the seed instead, and the harness
puts the same draws into the program's weights.

The forward is the published GraniteMoeHybrid model:

- token embeddings times ``embedding_multiplier``;
- per layer: RMSNorm, the mixer, the residual plus ``residual_multiplier``
  times the mixer's output; RMSNorm, SwiGLU MLP
  (``shared_intermediate_size``), the residual plus ``residual_multiplier``
  times the MLP's output;
- the mixer of a ``mamba`` layer is Mamba-2 written as its sequential
  recurrence over positions, not the chunked SSD algorithm: the depthwise
  causal conv with bias over x, B and C, then SiLU;
  dt = softplus(dt + dt_bias), A = -exp(A_log); per position
  h <- exp(dt A) h + dt x B^T and y = h C + D x; then the gated RMSNorm of
  y * silu(z) and the out-projection;
- the mixer of an ``attention`` layer is causal GQA with no position
  embedding (``position_embedding_type`` "nope") and softmax scale
  ``attention_multiplier``;
- final RMSNorm, the tied LM head, logits divided by ``logits_scaling``.

Departures from the published description:

- the Mamba in-projection is drawn as five matrices (z, x, B, C, dt) and
  its conv as two (x; B with C), as the program draws them; their
  concatenation is the published ``in_proj`` and ``conv1d``;
- the published Mamba-2 clamps dt to ``time_step_limit`` (0, inf), which
  is the identity here, and is left out;
- one group (``mamba_n_groups`` 1) and no projection biases
  (``mamba_proj_bias``, ``attention_bias`` false), as published; no
  experts (``num_local_experts`` 0).

``served_gaps`` reads, for each served token, how far its logit lies below
the reference's best at that position; with ``control=True`` also the gap
of the token that the control, the same forward with every projection's
operands rounded to float8_e4m3fn under a per-tensor scale, puts first.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
HEAD_ROWS = 64           # LM-head positions per block
SCALE_STD = 0.2          # norm scales and D: 1 + N(0, 0.2)
ATTN_NORM_GAIN = 4.0     # the norm before attention: 4 (1 + N(0, 0.2))
CONV_BIAS = 0.1          # conv biases: U(-0.1, 0.1)
A_RANGE = (1.0, 16.0)    # A ~ U(1, 16) (Mamba-2's initialization)
DT_RANGE = (1e-3, 1e-1)  # dt log-uniform (Mamba-2's initialization)
CONV_STD = 0.1           # the program's conv taps: N(0, 0.1)
EXTRA_STREAM = 0xB1A5    # folded into the seed's key for the extra leaves
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_BYTES = 4          # the recurrent state is float32


def padded_vocab(vocab: int) -> int:
    return (vocab + 255) // 256 * 256


def kinds_of(model: dict) -> tuple:
    """Each layer's mixer, ``mamba`` or ``attention``."""
    kinds = tuple(model["layer_types"])
    if len(kinds) != model["num_hidden_layers"] or \
            set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {kinds} do not describe "
                         f"{model['num_hidden_layers']} layers")
    return kinds


def _dims(model: dict) -> dict:
    d, h = model["hidden_size"], model["num_attention_heads"]
    di = model["mamba_expand"] * d
    return dict(d=d, H=h, KV=model["num_key_value_heads"], hd=d // h,
                ff=model["shared_intermediate_size"],
                L=model["num_hidden_layers"], V=model["vocab_size"],
                Vp=padded_vocab(model["vocab_size"]),
                eps=float(model["rms_norm_eps"]),
                di=di, N=model["mamba_d_state"], P=model["mamba_d_head"],
                Hs=model["mamba_n_heads"], K=model["mamba_d_conv"],
                M=kinds_of(model).count("mamba"),
                emb_mult=float(model["embedding_multiplier"]),
                res_mult=float(model["residual_multiplier"]),
                att_mult=float(model["attention_multiplier"]),
                logit_div=float(model["logits_scaling"]),
                dtype=jnp.dtype(model["torch_dtype"]))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _plan(kinds: tuple) -> list:
    """The program's layer stacks: [(kinds of one scan step, count)]."""
    n = len(kinds)
    for p in range(1, n // 2 + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return [(kinds[:p], n // p)]
    plan = []
    for k in kinds:
        if plan and plan[-1][0] == (k,):
            plan[-1] = ((k,), plan[-1][1] + 1)
        else:
            plan.append(((k,), 1))
    return plan


def layer_keys(model: dict, seed: int):
    """(embedding key, one key per layer in order), split as the program's
    initializer splits them."""
    plan = _plan(kinds_of(model))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4 + len(plan))
    keys = []
    for s, (period, count) in enumerate(plan):
        for k in jax.random.split(ks[2 + s], count):
            keys += list(jax.random.split(k, len(period)))
    return ks[0], keys


def _dense(key, shape, fan_in, dtype):
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def _mlp_weights(key, m):
    km = jax.random.split(key, 3)
    d, ff, dt = m["d"], m["ff"], m["dtype"]
    return {"wg": _dense(km[0], (d, ff), d, dt),
            "wu": _dense(km[1], (d, ff), d, dt),
            "wd": _dense(km[2], (ff, d), ff, dt)}


@partial(jax.jit, static_argnums=(1, 2))
def _layer_weights(layer_key, mt, kind):
    m = dict(mt)
    d, dt = m["d"], m["dtype"]
    block = jax.random.split(layer_key, 8)
    w = _mlp_weights(block[3], m)
    if kind == "attention":
        H, KV, hd = m["H"], m["KV"], m["hd"]
        ka = jax.random.split(block[0], 4)
        w.update(wq=_dense(ka[0], (d, H * hd), d, dt),
                 wk=_dense(ka[1], (d, KV * hd), d, dt),
                 wv=_dense(ka[2], (d, KV * hd), d, dt),
                 wo=_dense(ka[3], (H * hd, d), H * hd, dt))
        return w
    di, N, Hs, K = m["di"], m["N"], m["Hs"], m["K"]
    ks = jax.random.split(block[1], 8)

    def taps(k, c):
        return (jax.random.normal(k, (K, c), jnp.float32)
                * CONV_STD).astype(dt)
    w.update(w_z=_dense(ks[0], (d, di), d, dt),
             w_x=_dense(ks[1], (d, di), d, dt),
             w_B=_dense(ks[2], (d, N), d, dt),
             w_C=_dense(ks[3], (d, N), d, dt),
             w_dt=_dense(ks[4], (d, Hs), d, dt),
             conv_x=taps(ks[5], di), conv_BC=taps(ks[6], 2 * N),
             w_out=_dense(ks[7], (di, d), di, dt))
    return w


@partial(jax.jit, static_argnums=(2,))
def _extra_leaves(key, gain, mt):
    m = dict(mt)
    L, M, d, di, Hs, N, dt = (m["L"], m["M"], m["d"], m["di"], m["Hs"],
                              m["N"], m["dtype"])
    ks = jax.random.split(key, 10)

    def scale(k, shape, dtype=dt):
        return (1.0 + SCALE_STD * jax.random.normal(k, shape)).astype(dtype)

    def bias(k, n):
        return (CONV_BIAS * jax.random.uniform(k, (M, n), minval=-1.0,
                                               maxval=1.0)).astype(dt)
    a = jax.random.uniform(ks[3], (M, Hs), minval=A_RANGE[0],
                           maxval=A_RANGE[1])
    lo, hi = math.log(DT_RANGE[0]), math.log(DT_RANGE[1])
    step = jnp.exp(jax.random.uniform(ks[4], (M, Hs), minval=lo, maxval=hi))
    sign = jnp.where(jax.random.bernoulli(ks[9], 0.5, (d,)), 1.0, -1.0)
    ln1 = 1.0 + SCALE_STD * jax.random.normal(ks[0], (L, d))
    return {"ln1": (gain[:, None] * ln1).astype(dt),
            "ln2": scale(ks[1], (L, d)),
            "final": (sign * scale(ks[2], (d,), jnp.float32)).astype(dt),
            "A_log": jnp.log(a),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
            "D": scale(ks[5], (M, Hs), jnp.float32),
            "conv_x_bias": bias(ks[6], di),
            "conv_BC_bias": bias(ks[7], 2 * N),
            "norm": scale(ks[8], (M, di))}


def extra_leaves(model: dict, seed: int) -> dict:
    """The leaves the program's initializer leaves constant, drawn from the
    seed.  Per layer (leading axis over all layers): RMSNorm scales ``ln1``
    (before the mixer) and ``ln2`` (before the MLP), 1 + N(0, SCALE_STD),
    ``ln1`` of an attention layer times ATTN_NORM_GAIN: with the softmax
    scale 1/64 and unit inputs the scores would spread by about 1/8 and
    every position would weigh alike, so that no position embedding, or a
    wrong one, could be seen; ``final``, the final norm's scale, 1 + N(0,
    SCALE_STD) times a random sign per channel: with the embeddings times 12
    and the head tied, a scale of one sign puts the last input token's
    logit far above every other, and every answer would repeat one token.  Per Mamba layer (leading
    axis over the Mamba layers in order), float32: ``A_log`` = log A with
    A ~ U(A_RANGE), ``dt_bias`` = softplus^-1(dt) with dt log-uniform over
    DT_RANGE, ``D`` 1 + N(0, SCALE_STD); in the configuration's dtype: the
    conv biases ``conv_x_bias`` and ``conv_BC_bias`` ~ U(-CONV_BIAS,
    CONV_BIAS), the gated norm's scale ``norm`` 1 + N(0, SCALE_STD)."""
    m = _dims(model)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), EXTRA_STREAM)
    gain = jnp.asarray([ATTN_NORM_GAIN if k == "attention" else 1.0
                        for k in kinds_of(model)])
    return _extra_leaves(key, gain, tuple(sorted(m.items())))


@partial(jax.jit, static_argnums=(1,))
def _head_weights(embed_key, mt):
    """(embedding table, tied LM head as (d, V)) over the real
    vocabulary."""
    m = dict(mt)
    emb = (jax.random.normal(embed_key, (m["Vp"], m["d"]), jnp.float32)
           * 0.02).astype(m["dtype"])[: m["V"]]
    return emb, emb.T


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


def _f32(a):
    return a.astype(jnp.float32)


def _mlp(x, w, m, low):
    h = _rms(x, m["eps"]) * _f32(w["ln2"])
    g = _mm(h, w["wg"], low)
    u = _mm(h, w["wu"], low)
    return x + m["res_mult"] * _mm(jax.nn.silu(g) * u, w["wd"], low)


def _mamba_mixer(h, w, m, low):
    """Mamba-2 over (B, S, d) by its recurrence, one position at a time."""
    B, S, _ = h.shape
    Hs, P, N, K = m["Hs"], m["P"], m["N"], m["K"]
    z = _mm(h, w["w_z"], low)
    xbc = jnp.concatenate([_mm(h, w["w_x"], low), _mm(h, w["w_B"], low),
                           _mm(h, w["w_C"], low)], -1)
    taps = _f32(jnp.concatenate([w["conv_x"], w["conv_BC"]], -1))
    bias = _f32(jnp.concatenate([w["conv_x_bias"], w["conv_BC_bias"]]))
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * taps[i] for i in range(K)) + bias
    xi, Bm, Cm = jnp.split(jax.nn.silu(conv), [m["di"], m["di"] + N], -1)
    dt = jax.nn.softplus(_mm(h, w["w_dt"], low) + w["dt_bias"])  # (B,S,Hs)
    A = -jnp.exp(w["A_log"])
    xh = xi.reshape(B, S, Hs, P)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp              # (B,Hs,P) (B,Hs) (B,N) (B,N)
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    _, ys = jax.lax.scan(step, jnp.zeros((B, Hs, P, N), jnp.float32),
                         (xh.swapaxes(0, 1), dt.swapaxes(0, 1),
                          Bm.swapaxes(0, 1), Cm.swapaxes(0, 1)))
    y = ys.swapaxes(0, 1) + w["D"][:, None] * xh
    y = y.reshape(B, S, m["di"]) * jax.nn.silu(z)
    y = _rms(y, m["eps"]) * _f32(w["norm"])
    return _mm(y, w["w_out"], low)


def _attention_mixer(h, w, m, low):
    """Causal GQA with no position embedding, in blocks of query rows."""
    B, S, _ = h.shape
    H, KV, hd = m["H"], m["KV"], m["hd"]
    q = _mm(h, w["wq"], low).reshape(B, S, H, hd)
    k = jnp.repeat(_mm(h, w["wk"], low).reshape(B, S, KV, hd), H // KV, 2)
    v = jnp.repeat(_mm(h, w["wv"], low).reshape(B, S, KV, hd), H // KV, 2)
    pos = jnp.arange(S)
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, s0:s0 + Q_BLOCK],
                        k) * m["att_mult"]
        qpos = pos[s0:s0 + Q_BLOCK]
        sc = jnp.where(qpos[:, None] >= pos[None, :], sc, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v))
    att = jnp.concatenate(outs, 1).reshape(B, S, H * hd)
    return _mm(att, w["wo"], low)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, w, mt, kind, low):
    m = dict(mt)
    h = _rms(x, m["eps"]) * _f32(w["ln1"])
    mixer = _mamba_mixer if kind == "mamba" else _attention_mixer
    x = x + m["res_mult"] * mixer(h, w, m, low)
    return _mlp(x, w, m, low)


@partial(jax.jit, static_argnums=(2, 4))
def _logits(x, final, mt, head, low):
    m = dict(mt)
    return _mm(_rms(x, m["eps"]) * _f32(final), head, low) / m["logit_div"]


def _hidden(model: dict, seed: int, toks: np.ndarray, control: bool):
    """Final hidden states before the last norm, of the reference and (with
    ``control``) of the control, and what the LM head needs."""
    m = _dims(model)
    mt = tuple(sorted(m.items()))
    kinds = kinds_of(model)
    ek, keys = layer_keys(model, seed)
    extra = extra_leaves(model, seed)
    emb, head = _head_weights(ek, mt)
    x = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
    x = x * m["emb_mult"]
    del emb
    xs = [x, x] if control else [x]
    mi = 0
    for li, (kind, key) in enumerate(zip(kinds, keys)):
        w = _layer_weights(key, mt, kind)
        w.update(ln1=extra["ln1"][li], ln2=extra["ln2"][li])
        if kind == "mamba":
            w.update({n: extra[n][mi] for n in
                      ("A_log", "dt_bias", "D", "conv_x_bias",
                       "conv_BC_bias", "norm")})
            mi += 1
        xs = [_layer(xx, w, mt, kind, low)
              for xx, low in zip(xs, (False, True))]
        del w
    return xs, extra["final"], head, mt


def logits(model: dict, seed: int, toks) -> np.ndarray:
    """The reference's logits (B, S, V) over a token matrix (B, S)."""
    with jax.default_matmul_precision("highest"):
        (x,), final, head, mt = _hidden(model, seed, np.asarray(toks), False)
        return np.asarray(_logits(x, final, mt, head, False))


@partial(jax.jit, static_argnums=(5,))
def _head_gaps(x, head, final, served, valid, mt):
    """Per position: reference best minus the served token's logit, and
    minus the logit of the control's first choice (``x`` holds the two
    forwards' final hidden states)."""
    ref = _logits(x[0], final, mt, head, False)               # (B, R, V)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    ctrl = _logits(x[1], final, mt, head, True).argmax(-1)
    cgap = best - jnp.take_along_axis(ref, ctrl[..., None], -1)[..., 0]
    return jnp.where(valid, gap, 0.0), jnp.where(valid, cgap, 0.0)


def served_gaps(model: dict, seed: int, prompts, outs, out_max: int,
                control: bool = False) -> dict:
    """Teacher-force each prompt with its served tokens.

    ``prompts``: equal-length int arrays; ``outs``: the served token lists.
    Returns flat arrays over every served token: ``gap`` (reference best
    minus the served token's logit) and, with ``control``, ``control_gap``.
    """
    S = len(prompts[0])
    B = len(prompts)
    toks = np.zeros((B, S + out_max - 1), np.int32)
    served = np.zeros((B, out_max), np.int32)
    valid = np.zeros((B, out_max), bool)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        seq = np.concatenate([p, o[:-1]]).astype(np.int32)
        toks[i, : len(seq)] = seq
        served[i, : len(o)] = o
        valid[i, : len(o)] = True
    with jax.default_matmul_precision("highest"):
        xs, final, head, mt = _hidden(model, seed, toks, control)
        last = [xx[:, S - 1:] for xx in xs]
        if not control:
            last = [last[0], last[0]]
        gaps, cgaps = [], []
        for r0 in range(0, out_max, HEAD_ROWS):
            sl = slice(r0, r0 + HEAD_ROWS)
            g, c = _head_gaps(jnp.stack([last[0][:, sl], last[1][:, sl]]),
                              head, final, jnp.asarray(served[:, sl]),
                              jnp.asarray(valid[:, sl]), mt)
            gaps.append(np.asarray(g))
            cgaps.append(np.asarray(c))
    out = {"gap": np.concatenate(gaps, 1)[valid]}
    if control:
        out["control_gap"] = np.concatenate(cgaps, 1)[valid]
    return out


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shape:
    """Work a served token needs, as the algorithm needs it: 2 FLOPs per
    matmul parameter per token; per Mamba layer and token the depthwise
    conv (2 per tap and channel) and the recurrence (5 per state element:
    decay, the input's outer product and its add, then C's multiply-add);
    causal attention over the positions at or before the query's own in
    the attention layers; the LM head only where a logit is used.  Bytes of
    a decode step: every weight once; per live slot its embedding row, its
    K/V up to its position read and the new K/V written, and its recurrent
    state and conv tails read once and written once."""
    mamba_layers: int
    attention_layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    d_inner: int
    ssm_heads: int
    ssm_head_dim: int
    state: int
    conv: int
    vocab: int
    dtype_bytes: int

    @classmethod
    def of(cls, model: dict) -> "Shape":
        m = _dims(model)
        kinds = kinds_of(model)
        return cls(mamba_layers=kinds.count("mamba"),
                   attention_layers=kinds.count("attention"), d=m["d"],
                   heads=m["H"], kv_heads=m["KV"], head_dim=m["hd"],
                   ff=m["ff"], d_inner=m["di"], ssm_heads=m["Hs"],
                   ssm_head_dim=m["P"], state=m["N"], conv=m["K"],
                   vocab=m["V"],
                   dtype_bytes=DTYPE_BYTES[model["torch_dtype"]])

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.state

    @property
    def mlp_params(self) -> int:
        return 3 * self.d * self.ff

    @property
    def attention_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        return 2 * self.d * q + 2 * self.d * self.kv_heads * self.head_dim

    @property
    def mamba_matmul_params(self) -> int:
        """In-projection (z, x, B, C, dt) and out-projection."""
        return (self.d * (self.d_inner + self.conv_channels + self.ssm_heads)
                + self.d_inner * self.d)

    @property
    def state_elements(self) -> int:
        """One slot's recurrent state in one Mamba layer."""
        return self.ssm_heads * self.ssm_head_dim * self.state

    @property
    def token_flops(self) -> int:
        """One token through every layer, attention's scores and the LM
        head left out."""
        mamba = (2 * self.mamba_matmul_params
                 + 2 * self.conv * self.conv_channels
                 + 5 * self.state_elements)
        return (self.mamba_layers * mamba
                + self.attention_layers * 2 * self.attention_matmul_params
                + (self.mamba_layers + self.attention_layers)
                * 2 * self.mlp_params)

    def attention_flops(self, keys: int) -> int:
        """One query over ``keys`` positions in every attention layer."""
        return 4 * self.attention_layers * self.heads * self.head_dim * keys

    @property
    def head_flops(self) -> int:
        return 2 * self.d * self.vocab

    def prefill_flops(self, prompt: int) -> int:
        pairs = prompt * (prompt + 1) // 2
        return (self.token_flops * prompt + self.attention_flops(pairs)
                + self.head_flops)

    def decode_flops(self, pos: int) -> int:
        """One decoded token whose query sits at position ``pos``."""
        return (self.token_flops + self.attention_flops(pos + 1)
                + self.head_flops)

    def weight_bytes(self) -> int:
        """Every weight once: the matmuls, conv taps and biases, A_log, D,
        dt_bias, the gated norm's and the two RMSNorms' scales; the final
        norm and the tied LM head (the embedding's rows are counted per
        slot)."""
        mamba = (self.mamba_matmul_params
                 + (self.conv + 1) * self.conv_channels
                 + 3 * self.ssm_heads + self.d_inner)
        layers = (self.mamba_layers * mamba
                  + self.attention_layers * self.attention_matmul_params
                  + (self.mamba_layers + self.attention_layers)
                  * (self.mlp_params + 2 * self.d))
        return (layers + self.d + self.vocab * self.d) * self.dtype_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return (2 * self.attention_layers * self.kv_heads * self.head_dim
                * self.dtype_bytes)

    @property
    def state_bytes(self) -> int:
        """One slot's recurrent state and conv tails over the Mamba
        layers."""
        return self.mamba_layers * (
            self.state_elements * STATE_BYTES
            + (self.conv - 1) * self.conv_channels * self.dtype_bytes)

    def decode_slot_bytes(self, pos: int) -> int:
        """One live slot of a decode step at position ``pos``."""
        return (self.d * self.dtype_bytes
                + (pos + 2) * self.kv_bytes_per_token
                + 2 * self.state_bytes)
