"""vChunk-style streamed matmul Pallas kernel (TPU target).

The paper's vChunk insight — NPU DMA moves model weights HBM->SRAM in large
monotonically-advancing chunks (Patterns 1/2), re-walked per iteration
(Pattern 3) — maps onto the TPU memory hierarchy as a *grid-pipelined
weight stream*: the K-major grid walks the weight matrix range by range,
`pl.pallas_call`'s automatic pipelining double-buffers the HBM->VMEM DMAs
(the range-TLB-friendly sequential stream), and a VMEM fp32 accumulator
plays the scratchpad.  Block shapes are MXU-aligned (multiples of 128 on
the contracting/lane dims).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import fit_block


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def streamed_matmul(x: jnp.ndarray, w: jnp.ndarray, *,
                    block_m: int = 256, block_n: int = 256,
                    block_k: int = 512,
                    interpret: bool = False) -> jnp.ndarray:
    """x: (M,K) @ w: (K,N) -> (M,N) in x.dtype, fp32 VMEM accumulation.

    Weight traffic: each (k, n) weight block is streamed HBM->VMEM exactly
    M/block_m times; K-major ordering keeps the address walk monotonic per
    output tile (the vChunk Pattern-2 stream), and the grid restart per
    output row-band is Pattern-3's iteration loop.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    # M is a sublane dim (8); K and N are lane dims of x / w / out (128)
    bm, bn, bk = fit_block(M, block_m, 8), fit_block(N, block_n, 128), \
        fit_block(K, block_k, 128)
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)

    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
