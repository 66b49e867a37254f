"""Mixture-of-Experts FFN with expert parallelism (EP) over the ``model``
mesh axis.

Dispatch is capacity-based (GShard-style, drop-on-overflow): tokens stay
sharded over ``data``; experts are sharded over ``model``; the
dispatch/return traffic is two explicit `all_to_all`s inside a `shard_map`
— exactly the "critical edge" traffic pattern the paper's heterogeneous
EdgeMatch penalizes for (§4.3), as a first-class JAX collective.

Covers both assigned MoE archs:
  * deepseek-moe-16b — 2 shared + 64 routed, top-6, fine-grained (d_ff 1408)
  * llama4-maverick  — 1 shared + 128 routed, top-1 (d_ff 8192)

The single-device path (no mesh) runs the same math with the all_to_alls
elided — that is the oracle the EP path is tested against.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .common import Params, dense_init, get_moe_ff_axis


def moe_init(cfg, key, dtype) -> Tuple[Params, Dict]:
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 7)
    p = {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "wg": dense_init(ks[1], (E, d, f), dtype),
        "wu": dense_init(ks[2], (E, d, f), dtype),
        "wd": dense_init(ks[3], (E, f, d), dtype, in_axis=1),
    }
    ax = {
        "router": ("embed", None),
        "wg": ("expert", "embed", "moe_ff"),
        "wu": ("expert", "embed", "moe_ff"),
        "wd": ("expert", "moe_ff", "embed"),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared_wg"] = dense_init(ks[4], (d, fs), dtype)
        p["shared_wu"] = dense_init(ks[5], (d, fs), dtype)
        p["shared_wd"] = dense_init(ks[6], (fs, d), dtype, in_axis=0)
        ax["shared_wg"] = ("embed", "ff")
        ax["shared_wu"] = ("embed", "ff")
        ax["shared_wd"] = ("ff", "embed")
    return p, ax


def _expert_ffn(x, wg, wu, wd, ff_axis: Optional[str] = None):
    """x: (E_loc, C, d); weights (E_loc, d, f[/N])/(E_loc, f[/N], d).

    With ``ff_axis`` (TP/EP recipe) the hidden dim f is sharded over that
    mesh axis: the down-projection's partial sums reduce with a psum of the
    *activations* — expert weights never leave their shard.
    """
    g = jnp.einsum("ecd,edf->ecf", x, wg)
    u = jnp.einsum("ecd,edf->ecf", x, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, wd)
    if ff_axis is not None:
        y = jax.lax.psum(y, ff_axis)
    return y


def _capacity(n_tokens: int, top_k: int, n_experts: int, factor: float) -> int:
    c = math.ceil(n_tokens * top_k / n_experts * factor)
    return max(8, c)


def _local_moe(cfg, x_flat, router_w, wg, wu, wd, *,
               model_size: int, model_axis: Optional[str],
               ff_axis: Optional[str] = None):
    """Per-device MoE over local tokens.  When ``model_axis`` is set, wg/wu/wd
    hold E/model_size local experts and dispatch crosses shards via
    all_to_all; otherwise all experts are local."""
    T, d = x_flat.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = (x_flat.astype(jnp.float32) @ router_w)            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)                         # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / (T * k))
    aux = E * jnp.sum(me * ce)

    C = _capacity(T, k, E, cfg.capacity_factor)
    e_flat = idx.reshape(-1)                                     # (T*k,)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                    # exclusive
    pos_flat = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
    keep = pos_flat < C
    pos_c = jnp.where(keep, pos_flat, 0)

    tok_idx = jnp.repeat(jnp.arange(T), k)
    buf = jnp.zeros((E, C, d), x_flat.dtype)
    buf = buf.at[e_flat, pos_c].add(
        x_flat[tok_idx] * keep[:, None].astype(x_flat.dtype))

    if model_axis is not None and model_size > 1:
        # (E, C, d) -> (E/M, C*M, d): each shard receives its experts' slices
        buf = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                                 concat_axis=1, tiled=True)
    out_buf = _expert_ffn(buf, wg, wu, wd, ff_axis=ff_axis)
    if model_axis is not None and model_size > 1:
        out_buf = jax.lax.all_to_all(out_buf, model_axis, split_axis=1,
                                     concat_axis=0, tiled=True)

    picked = out_buf[e_flat, pos_c]                              # (T*k, d)
    picked = picked * (keep[:, None] * gate.reshape(-1)[:, None]
                       ).astype(picked.dtype)
    y = picked.reshape(T, k, d).sum(axis=1)
    return y.astype(x_flat.dtype), aux


def moe_forward(cfg, p: Params, x: jnp.ndarray, *,
                mesh: Optional[Mesh] = None,
                data_spec: Tuple = ("data",),
                model_axis: str = "model") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,d) -> (y, aux_loss).  Routed experts via EP shard_map when a
    mesh is provided; shared experts run as a plain TP-sharded dense FFN.
    """
    B, S, d = x.shape

    if mesh is not None and model_axis in mesh.axis_names and \
            mesh.shape[model_axis] > 1:
        M = mesh.shape[model_axis]
        # Split the sequence over the model axis too: each device dispatches a
        # DISTINCT token slice, so expert FLOPs are not replicated M times.
        # (Decode steps have S=1 — replicate there; the redundancy is one
        # token per device.)
        split_seq = S % M == 0
        dp = P(data_spec, model_axis if split_seq else None, None)
        ff_axis = get_moe_ff_axis()

        def body(xl, rw, wg, wu, wd):
            T = xl.shape[0] * xl.shape[1]
            y, aux = _local_moe(cfg, xl.reshape(T, d), rw, wg, wu, wd,
                                model_size=M, model_axis=model_axis,
                                ff_axis=ff_axis)
            # aux is per-device; average across the whole mesh
            aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
            return y.reshape(xl.shape), aux

        # expert weights: E over model; hidden dim optionally sharded over
        # ``ff_axis`` (the TP/EP recipe — no FSDP gathers at the boundary)
        wg_spec = P(model_axis, None, ff_axis)
        wd_spec = P(model_axis, ff_axis, None)
        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(dp, P(), wg_spec, wg_spec, wd_spec),
            out_specs=(dp, P()),
            check_vma=False,
        )(x, p["router"], p["wg"], p["wu"], p["wd"])
    else:
        y, aux = _local_moe(cfg, x.reshape(B * S, d), p["router"],
                            p["wg"], p["wu"], p["wd"],
                            model_size=1, model_axis=None)
        y = y.reshape(B, S, d)

    if cfg.n_shared_experts:
        g = jnp.einsum("bsd,df->bsf", x, p["shared_wg"])
        u = jnp.einsum("bsd,df->bsf", x, p["shared_wu"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        y = y + jnp.einsum("bsf,fd->bsd", h, p["shared_wd"])
    return y, aux
