"""Mamba-2 decode-step state update, in place in a stacked cache (TPU target).

A decode step moves each Mamba-2 layer's whole (B, H, P, N) float32 state
from HBM and back, and little else: the update is memory-bound.  This
kernel reads each (hb, P, N) block of one layer's state once, computes

    new = state * exp(dt * A) + (x * dt) (outer) B,    y = sum_n new * C

in VMEM, and stores ``new`` to the block it came from: the stacked
(L, B, H, P, N) slab is aliased to the kernel's first output, so in a
donated cache the state crosses HBM once each way.  The layer index is a
scalar-prefetch operand in SMEM, so one compiled kernel serves every
layer of a stack.  The math is that of the jnp update in ``models/ssd.py``,
all in float32; only the order of the products (x * dt first) and of y's
sum may round differently.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import fit_block

# the kernel's name, which its call carries in a compiled program
KERNEL = "ssd_state_update"


def _state_kernel(layer_ref, s_ref, xdt_ref, da_ref, b_ref, c_ref,
                  o_ref, y_ref):
    new = s_ref[...] * da_ref[...][:, :, None] + \
        xdt_ref[...][:, :, None] * b_ref[...]        # (hb, P, N)
    o_ref[...] = new
    y_ref[...] = jnp.sum(new * c_ref[...], axis=-1)  # (hb, P)


def ssd_state_update(state, layer, xdt, dA, B, C, *,
                     interpret: bool = False):
    """state (L, Bt, H, P, N) float32, updated at layer ``layer`` in place;
    xdt (Bt, H, P) = x * dt; dA (Bt, H) = exp(dt * A); B, C (Bt, N).

    ``layer`` may be a traced int32 scalar.  Returns (state, y (Bt, H, P)
    float32).
    """
    _, Bt, H, P, N = state.shape
    hb = fit_block(H, 16, 8)
    f32 = jnp.float32
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    block = (None, hb, P)
    return pl.pallas_call(
        _state_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Bt, H // hb),
            in_specs=[
                pl.BlockSpec((None, None, hb, P, N),
                             lambda b, h, l: (l[0], b, h, 0, 0)),
                pl.BlockSpec(block, lambda b, h, l: (b, h, 0)),
                pl.BlockSpec((None, hb, 1), lambda b, h, l: (b, h, 0)),
                pl.BlockSpec((None, 1, N), lambda b, h, l: (b, 0, 0)),
                pl.BlockSpec((None, 1, N), lambda b, h, l: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, hb, P, N),
                             lambda b, h, l: (l[0], b, h, 0, 0)),
                pl.BlockSpec(block, lambda b, h, l: (b, h, 0)),
            ]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((Bt, H, P), f32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name=KERNEL,
        interpret=interpret,
    )(layer, state, xdt.astype(f32), dA.astype(f32)[..., None],
      B.astype(f32)[:, None, :], C.astype(f32)[:, None, :])
