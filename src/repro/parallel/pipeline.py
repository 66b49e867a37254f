"""GPipe-style pipeline parallelism over the ``pod`` axis.

For multi-pod training an alternative to pure DP-across-pods: pods hold
disjoint layer ranges and microbatches stream through a
`collective_permute` pipeline.  Implemented as a generic combinator over a
per-stage function; the scan over (microbatches + bubble steps) gives the
classic (P-1)/(P-1+m) bubble fraction.

This is an opt-in recipe (examples + §Perf candidates), not the default
mesh layout — the dry-run's baseline keeps pods data-parallel.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, x_micro: jnp.ndarray, *,
                     mesh: Mesh, axis: str = "pod",
                     stage_params=None) -> jnp.ndarray:
    """Run ``stage_fn(params_local, x)`` as a P-stage pipeline.

    x_micro: (n_micro, micro_batch, ...) — microbatches stream in sequence.
    stage_params: pytree whose leading dim is the stage count (sharded over
    ``axis``).  Returns the pipeline output microbatches (same shape),
    valid after the (P-1)-step fill.
    """
    Pn = mesh.shape[axis]
    n_micro = x_micro.shape[0]

    def body(params_l, xm):
        sidx = jax.lax.axis_index(axis)
        total = n_micro + Pn - 1
        perm = [(i, i + 1) for i in range(Pn - 1)]

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (others use the permuted buffer)
            feed = jnp.where(t < n_micro, t, n_micro - 1)
            x_in = jnp.where(sidx == 0, xm[feed], buf)
            y = stage_fn(jax.tree.map(lambda a: a[0], params_l), x_in)
            buf_next = jax.lax.ppermute(y, axis, perm)
            # last stage emits after the fill
            emit = t - (Pn - 1)
            emit_ok = (emit >= 0) & (sidx == Pn - 1)
            outs = jax.lax.cond(
                emit_ok,
                lambda o: o.at[jnp.maximum(emit, 0)].set(y),
                lambda o: o, outs)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(xm[0])
        outs0 = jnp.zeros_like(xm)
        (_, outs), _ = jax.lax.scan(step, (buf0, outs0),
                                    jnp.arange(total))
        # broadcast final outputs from the last stage to all pods (masked sum)
        outs = jnp.where(sidx == Pn - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis)
        return outs

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(axis), P()),
                         out_specs=P(),
                         check_vma=False)(stage_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Classic GPipe bubble: (P-1) / (P-1+m)."""
    return (n_stages - 1) / (n_stages - 1 + n_micro)
