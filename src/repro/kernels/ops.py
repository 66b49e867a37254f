"""Jit'd public wrappers for the Pallas kernels.

The kernels lower to Mosaic for the TPU.  ``interpret=True`` runs the
kernel bodies in Python through the Pallas interpreter instead, which is
how the CPU tests validate them; nothing picks it on the caller's behalf.
"""
from __future__ import annotations

import functools

import jax

from .decode_attention import decode_attention as _decode
from .flash_attention import flash_attention as _flash
from .ssd_scan import ssd_scan as _ssd
from .streamed_matmul import streamed_matmul as _matmul


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def matmul(x, w, *, block_m=256, block_n=256, block_k=512, interpret=False):
    return _matmul(x, w, block_m=block_m, block_n=block_n, block_k=block_k,
                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=256, block_k=256,
                    interpret=False):
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk=256, interpret=False):
    return _ssd(x, dt, A, B, C, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q, k, v, length, *, block_s=512, interpret=False):
    """``length`` is traced: one compile serves every decode position."""
    return _decode(q, k, v, length, block_s=block_s, interpret=interpret)
