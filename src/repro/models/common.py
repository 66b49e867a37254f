"""Shared model building blocks (pure JAX, no flax).

Parameters are plain dict pytrees.  Every initializer returns
``(params, logical_axes)`` where ``logical_axes`` mirrors the param tree with
tuples of *logical axis names* per dimension; ``repro.parallel.sharding``
maps those to mesh PartitionSpecs.  This is the MaxText-style logical-axis
indirection that lets one model definition serve every mesh.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]
Axes = Dict[str, Any]


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape: Sequence[int], dtype, in_axis: int = -2) -> jnp.ndarray:
    """Truncated-normal fan-in init (what llama-family checkpoints resemble)."""
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
              eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg, x: jnp.ndarray, p: Params) -> jnp.ndarray:
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.rms_eps)
    return rmsnorm(x, p["scale"], cfg.rms_eps)


def norm_init(cfg, d: int, dtype) -> Tuple[Params, Axes]:
    if cfg.norm == "layernorm":
        return ({"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
                {"scale": ("embed",), "bias": ("embed",)})
    return ({"scale": jnp.ones((d,), dtype)}, {"scale": ("embed",)})


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# mesh context (logical names resolved lazily)
# ---------------------------------------------------------------------------

_ACTIVATION_RULES: Dict[str, Optional[Any]] = {}
_MESH_CTX: Dict[str, Any] = {"mesh": None, "data_spec": ("data",),
                             "model_axis": "model", "moe_ff_axis": None}


def set_activation_rules(rules: Dict[str, Optional[Any]]) -> None:
    """Install logical->mesh rules for activation constraints (see
    parallel/sharding.py).  No-op outside a mesh context."""
    global _ACTIVATION_RULES
    _ACTIVATION_RULES = dict(rules)


def set_mesh_context(mesh, data_spec=("data",), model_axis="model",
                     moe_ff_axis=None) -> None:
    """Install the mesh used by shard_map-based modules (attention, MoE).
    ``data_spec`` is the tuple of mesh axes that shard the batch dim
    (("pod","data") on the multi-pod mesh).  ``moe_ff_axis`` shards the
    expert hidden dim (TP/EP recipe: expert weights 2D-sharded, no
    gathers)."""
    _MESH_CTX["mesh"] = mesh
    _MESH_CTX["data_spec"] = tuple(data_spec)
    _MESH_CTX["model_axis"] = model_axis
    _MESH_CTX["moe_ff_axis"] = moe_ff_axis


def get_mesh_context():
    return (_MESH_CTX["mesh"], _MESH_CTX["data_spec"], _MESH_CTX["model_axis"])


def get_moe_ff_axis():
    return _MESH_CTX["moe_ff_axis"]


def clear_mesh_context() -> None:
    _MESH_CTX["mesh"] = None
    set_activation_rules({})


_SCAN_UNROLL = {"on": False}


def set_scan_unroll(on: bool) -> None:
    """Dry-run roofline mode: fully unroll layer scans so XLA cost analysis
    sees every layer (while-loop bodies are otherwise counted once).  Used
    only for the small-L calibration lowers in launch/dryrun.py."""
    _SCAN_UNROLL["on"] = bool(on)


def get_scan_unroll() -> bool:
    return _SCAN_UNROLL["on"]


def with_logical_constraint(x: jnp.ndarray, *logical_axes: Optional[str]):
    """Apply with_sharding_constraint if rules are installed; identity
    otherwise (lets the same model run un-meshed in unit tests).  With rules
    installed a mesh must be in scope (``jax.set_mesh``): a constraint that
    cannot be applied raises."""
    if not _ACTIVATION_RULES:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(*[_ACTIVATION_RULES.get(a) if a else None for a in logical_axes])
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                          vocab_size: int) -> jnp.ndarray:
    """Token-level CE with padded-vocab masking (iota mask — no copies, stays
    shardable when the vocab dim is model-sharded)."""
    logits = logits.astype(jnp.float32)
    vpad = logits.shape[-1]
    if vpad > vocab_size:
        iota = jax.lax.broadcasted_iota(jnp.int32, (vpad,), 0)
        logits = jnp.where(iota < vocab_size, logits, -1e9)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, vpad, dtype=logits.dtype)
    picked = jnp.sum(logits * onehot, axis=-1)
    return lse - picked
