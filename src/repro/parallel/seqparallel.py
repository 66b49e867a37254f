"""Sequence-parallel SSD scan: shard the 524k-token sequence across mesh
devices and chain SSM states through `collective_permute` (SP for the
long_500k shape).

Two-pass formulation (linear-recurrence prefix over devices):

  pass 1: each device runs its local chunk scan from a zero state,
          producing its local final state S_i and total decay D_i.
  chain:  an M-step ppermute pipeline forms the exclusive prefix
          state_in_i = sum_{j<i} S_j * prod_{j<k<i} D_k.
  pass 2: re-run the local scan seeded with state_in_i.

Pass 2 recomputes the local work (the classic parallel-scan 2x trade), so
wall-clock = 2x local + M p2p hops instead of 1x serial over the whole
sequence — a 8x win at M=16 shards.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.ssd import ssd_scan_ref


def _local_decay(dt: jnp.ndarray, A: jnp.ndarray) -> jnp.ndarray:
    """Total per-head decay of a local sequence shard: exp(sum_t dt_t * A)."""
    return jnp.exp(jnp.einsum("bsh,h->bh", dt, A))


def seq_parallel_ssd(x, dt, A, B, C, *, chunk: int, mesh: Mesh,
                     axis: str = "data") -> jnp.ndarray:
    """x: (b,S,H,P); dt: (b,S,H); B/C: (b,S,G,N).  S sharded over ``axis``.

    Returns y: (b,S,H,P) (same sharding).  Exact: matches the single-device
    ssd_scan_ref (tests/test_seqparallel.py).
    """
    M = mesh.shape[axis]

    def body(x_l, dt_l, A_r, B_l, C_l):
        # pass 1: local state from zero init
        _, s_local = ssd_scan_ref(x_l, dt_l, A_r, B_l, C_l, chunk,
                                  return_state=True)
        d_local = _local_decay(dt_l, A_r)                   # (b,H)

        # exclusive prefix chain: state_in_i = S_{i-1} + D_{i-1}*state_in_{i-1}
        # as an (M-1)-hop ppermute pipeline (device 0 receives zeros).
        perm = [(i, i + 1) for i in range(M - 1)]
        carry = jnp.zeros_like(s_local)
        for _ in range(M - 1):
            send = s_local + carry * d_local[..., None, None]
            carry = jax.lax.ppermute(send, axis, perm)
        state_in = carry

        # pass 2: seeded local scan (the 2x recompute of parallel scan)
        y, _ = ssd_scan_ref(x_l, dt_l, A_r, B_l, C_l, chunk,
                            init_state=state_in, return_state=True)
        return y

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis, None, None), P(None, axis, None),
                  P(), P(None, axis, None, None), P(None, axis, None, None)),
        out_specs=P(None, axis, None, None),
        check_vma=False,
    )(x, dt, A, B, C)
