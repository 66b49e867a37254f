"""Logical-axis -> mesh-axis sharding rules (MaxText-style indirection).

One model definition, any mesh.  Params carry logical axis names (see
models/*.py ``*_init``); this module maps them to PartitionSpecs for a given
mesh and parallelism recipe.

Baseline recipe (paper-faithful tenant layout; §Perf iterates on it):
  * vocab / fused-head / ff / expert dims  -> "model"   (TP / EP)
  * d_model (param) dim                    -> "data"    (FSDP / ZeRO-3)
  * batch                                  -> "data"
  * attention q-sequence + split-KV cache  -> "model"   (inside shard_map /
                                               decode constraints)
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("data",)


def param_rules(mesh: Mesh, *, fsdp: bool = True) -> Dict[str, Any]:
    """fsdp=True: ZeRO-3 baseline (d_model dim sharded over data; per-layer
    all-gathers).  fsdp=False: TP/EP-only recipe — params replicated over
    data except expert hidden dims, which shard over data with activation
    psums (no weight gathers at all)."""
    return {
        "vocab": "model",
        "embed": "data" if fsdp else None,
        "heads": "model",
        "kv_heads": "model",
        "ff": "model",
        "moe_ff": None if fsdp else "data",
        "expert": "model",
        "layers": None,
        None: None,
    }


def activation_rules(mesh: Mesh) -> Dict[str, Any]:
    return {
        "batch": batch_axes(mesh),
        "seq": "model",
        "vocab_act": "model",
        "heads_act": "model",
    }


def logical_to_spec(axes: Tuple, rules: Dict[str, Any]) -> P:
    return P(*[rules.get(a) for a in axes])


def param_specs(logical_axes, rules: Dict[str, Any]):
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(lambda t: logical_to_spec(t, rules), logical_axes,
                        is_leaf=lambda t: isinstance(t, tuple))


def named_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# cache / batch specs (decode)
# ---------------------------------------------------------------------------

def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fit(mesh: Mesh, axes, dim_size: int):
    """Use ``axes`` for a dim only if the dim is divisible by their size
    (a batch of 1 stays unsharded)."""
    return axes if dim_size % _axes_size(mesh, axes) == 0 else None


def cache_spec_for(leaf_path: str, shape, mesh: Mesh) -> P:
    """Sharding for decode-cache leaves.

    KV caches (L, B, S, KV*hd): batch over data axes, *sequence over model*
    (split-KV).  SSM states (L, B, H, P, N): heads over model.  Conv tails
    and cross-attention caches: batch only.  Leading dim = stacked layers
    (unsharded).  Dims that don't divide the mesh axes stay replicated.
    """
    ba = batch_axes(mesh)
    ndim = len(shape)
    if leaf_path in ("k", "v"):
        return P(None, _fit(mesh, ba, shape[1]),
                 _fit(mesh, "model", shape[2]), None)
    if leaf_path == "state":
        return P(None, _fit(mesh, ba, shape[1]),
                 _fit(mesh, "model", shape[2]), None, None)
    if leaf_path in ("cross_k", "cross_v", "conv_x", "conv_BC"):
        return P(None, _fit(mesh, ba, shape[1]), *([None] * (ndim - 2)))
    return P(*([None] * ndim))


def cache_specs(cache_shapes, mesh: Mesh):
    """Build PartitionSpecs for the (stacked) decode cache pytree."""
    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return cache_spec_for(name, leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def batch_specs(batch_shapes, mesh: Mesh):
    """Input batches: shard the leading (batch) dim over ``data``."""
    ba = batch_axes(mesh)

    def one(leaf):
        if leaf.ndim == 0:
            return P()
        return P(_fit(mesh, ba, leaf.shape[0]), *([None] * (leaf.ndim - 1)))
    return jax.tree.map(one, batch_shapes)
