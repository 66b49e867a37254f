"""Split-KV decode attention Pallas kernel (TPU target).

Decode is memory-bound: the whole job is streaming the KV cache HBM->VMEM
once and doing one dot per block.  The grid walks cache blocks sequentially
per (batch*head); partial (max, sum, acc) live in VMEM scratch — the
single-token analogue of flash attention, and the kernel the split-KV
sharding scheme expects per shard.  The valid length arrives as a
scalar-prefetch operand in SMEM, so one compiled kernel serves every
decode position.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import fit_block

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, block_s: int, n_s: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                 # (1, hd)
    k = k_ref[0].astype(jnp.float32)                 # (bs, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (1, bs)
    pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    s = jnp.where(pos < len_ref[0], s, NEG_INF)
    m_prev = m_ref[...]                               # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    r = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * r + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * r + \
        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(si == n_s - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     length, *, block_s: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B,H,hd); k,v: (B,S,H,hd); attends to cache positions < length.

    ``length`` may be a traced int32 scalar.  Matches
    ref.decode_attention_ref.
    """
    B, S, H, hd = k.shape
    bs = fit_block(S, block_s, 8)
    n_s = S // bs
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(B * H, 1, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    lens = jnp.asarray(length, jnp.int32).reshape(1)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_s=bs, n_s=n_s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, n_s),
            in_specs=[
                pl.BlockSpec((1, 1, hd), lambda b, s, n: (b, 0, 0)),
                pl.BlockSpec((1, bs, hd), lambda b, s, n: (b, s, 0)),
                pl.BlockSpec((1, bs, hd), lambda b, s, n: (b, s, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, hd), lambda b, s, n: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(B, H, hd)
