"""Causal flash attention Pallas kernel (TPU target).

Blockwise online softmax with running (max, sum, acc) held in VMEM scratch.
Unlike the jnp reference path (which must evaluate every (q, kv) block and
mask), the kernel *skips* fully-masked blocks via the grid index map — on
TPU the causal triangle costs ~S^2/2, recovering the 2x the XLA path wastes
(this is the compute-term optimization for prefill cells; see §Perf).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  n_k: int):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: block row qi only needs kv blocks with start <= q block end
    run = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)                 # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                 # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_blk = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_ref[...], m_blk)
        p = jnp.exp(s - m_new[:, None])
        r_old = jnp.exp(m_ref[...] - m_new)
        l_new = l_ref[...] * r_old + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * r_old[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_k - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 256,
                    block_k: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """q,k,v: (B,H,S,hd) -> (B,H,S,hd).  GQA callers broadcast KV heads in
    the ops wrapper; hd should be a multiple of 128 for MXU alignment (64
    also lowers, at half MXU occupancy)."""
    B, H, S, hd = q.shape
    assert k.shape == v.shape == (B, H, S, hd)
    bq, bk = fit_block(S, block_q, 8), fit_block(S, block_k, 8)
    n_k = S // bk
    grid = (B * H, S // bq, n_k)
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, S, hd)
    vf = v.reshape(B * H, S, hd)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=bq,
                          block_k=bk, causal=causal, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd)
