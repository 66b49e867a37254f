"""Mamba-2 SSD chunk-scan Pallas kernel (TPU target).

One grid step = one (batch, head, chunk) tile.  The chunk axis is the
innermost, *sequential* grid dimension: the running SSM state (P x N) lives
in VMEM scratch and persists across chunk iterations of the same (b, h) —
the TPU-native equivalent of the paper's scratchpad-resident data flow
(state never round-trips HBM between chunks).

Intra-chunk math matches models.ssd.ssd_scan_ref for n_groups=1, with the
(q x q) decay matrix built in VMEM; the MXU sees three (q x q) / (q x P) /
(q x N) matmuls per tile.  ``dt`` enters twice, as a (1, q) row and a
(q, 1) column, so that both prefix sums come from masked reductions of
one (q x q) tile with no in-kernel transpose; the per-head ``A`` is a
scalar-prefetch operand in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(A_ref, x_ref, dtr_ref, dtc_ref, B_ref, C_ref, y_ref,
                state_ref, *, chunk: int, n_heads: int):
    bh, ci = pl.program_id(0), pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    A = A_ref[bh % n_heads]
    x = x_ref[0, 0].astype(jnp.float32)          # (q, P)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)   # (1, q)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)   # (q, 1)
    Bm = B_ref[0, 0].astype(jnp.float32)         # (q, N)
    Cm = C_ref[0, 0].astype(jnp.float32)         # (q, N)

    dA_row, dA_col = dt_row * A, dt_col * A
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = iota_i >= iota_j
    # inclusive prefix sums of dA, as a column (cum_i) and as a row (cum_j)
    cum_col = jnp.sum(jnp.where(lower, dA_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(iota_i <= iota_j, dA_col, 0.0), axis=0,
                      keepdims=True)
    cum_last = jnp.sum(dA_row, axis=1, keepdims=True)   # (1, 1)
    xdt = x * dt_col

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for i >= j
    L = jnp.where(lower, jnp.exp(cum_col - cum_row), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (q,q)
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())))

    # inter-chunk: contribution of the carried state
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        Cm, state_ref[...], (((1,), (1,)), ((), ())))     # (q,N)x(P,N)->(q,P)

    # state update: S' = S * exp(sum dA) + sum_j exp(cum_last - cum_j) xdt_j B_j
    dec = jnp.exp(cum_last - cum_col)             # (q, 1)
    contrib = jax.lax.dot_general(xdt * dec, Bm,
                                  (((0,), (0,)), ((), ())))  # (P, N)
    state_ref[...] = state_ref[...] * jnp.exp(cum_last) + contrib
    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, *, chunk: int = 256,
             interpret: bool = False) -> jnp.ndarray:
    """x: (b,S,H,P); dt: (b,S,H); A: (H,); B/C: (b,S,N) (n_groups=1).

    Returns y: (b,S,H,P) matching ref.ssd_scan_kernel_ref.
    """
    b, S, H, P = x.shape
    N = B.shape[-1]
    q = min(chunk, S)
    while S % q:
        q -= 1
    nc = S // q

    xg = x.transpose(0, 2, 1, 3).reshape(b * H, nc, q, P)
    dtg = dt.transpose(0, 2, 1).reshape(b * H, nc, q)
    per_bh = lambda bh, c, A_ref: (bh, c, 0, 0)
    per_b = lambda bh, c, A_ref: (bh // H, c, 0, 0)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=q, n_heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * H, nc),
            in_specs=[
                pl.BlockSpec((1, 1, q, P), per_bh),
                pl.BlockSpec((1, 1, 1, q), per_bh),
                pl.BlockSpec((1, 1, q, 1), per_bh),
                pl.BlockSpec((1, 1, q, N), per_b),
                pl.BlockSpec((1, 1, q, N), per_b),
            ],
            out_specs=pl.BlockSpec((1, 1, q, P), per_bh),
            scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b * H, nc, q, P), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), xg,
      dtg.reshape(b * H, nc, 1, q), dtg.reshape(b * H, nc, q, 1),
      B.reshape(b, nc, q, N), C.reshape(b, nc, q, N))
    return out.reshape(b, H, S, P).transpose(0, 2, 1, 3)
