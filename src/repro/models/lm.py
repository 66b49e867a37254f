"""Unified language model covering all assigned decoder-only families
(dense / moe / ssm / hybrid / vlm); the whisper encoder-decoder lives in
``whisper.py`` and reuses the same blocks.

Layer stacks are scanned (`lax.scan` over stacked params) with per-layer
remat — HLO stays compact for 48-layer models and activation memory is
bounded by one layer.  MoE interleaving (llama4) scans over (dense, moe)
*pairs* so the stack stays homogeneous.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from .attention import update_cache
from .blocks import block_forward, block_init, init_block_cache
from .common import (Params, apply_norm, dtype_of, embed_init, norm_init,
                     softmax_cross_entropy, with_logical_constraint)

# decode-cache leaves that a decode step rewrites whole, layer by layer: the
# SSM state, which a layer's step takes for the whole stack and hands back
# with its own layer rewritten (in place, on a TPU: ssd.update_state), and
# the conv tails, sliced out per layer and written back
STATES = ("state", "conv_x", "conv_BC")


def layer_slice(cache: Dict[str, jnp.ndarray], layer) -> Dict[str, Any]:
    """Layer ``layer`` of each leaf of a stacked cache."""
    return {n: jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
            for n, c in cache.items()}


def write_layer(cache: Dict[str, jnp.ndarray], layer,
                new: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
    """``cache`` with layer ``layer`` of each leaf named in ``new``
    overwritten: one ``dynamic_update_slice`` per leaf, in place when the
    cache is donated."""
    return {**cache, **{n: jax.lax.dynamic_update_index_in_dim(
        cache[n], v.astype(cache[n].dtype), layer, 0)
        for n, v in new.items()}}


def layer_plan(cfg) -> List[Tuple[Tuple[str, ...], int]]:
    """[(kinds-per-scan-step, count), ...] — homogeneous scan stacks.

    The layer kinds' shortest period, scanned as one stack, where they
    repeat it more than once (uniform stacks, llama4's interleave,
    granite's mamba x5, attention, mamba x4); else a stack per run of
    consecutive layers of one kind (deepseek's leading dense layer)."""
    kinds = list(cfg.block_kinds())
    n = len(kinds)
    for p in range(1, n // 2 + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return [(tuple(kinds[:p]), n // p)]
    plan: List[Tuple[Tuple[str, ...], int]] = []
    for k in kinds:
        if plan and plan[-1][0] == (k,):
            plan[-1] = ((k,), plan[-1][1] + 1)
        else:
            plan.append(((k,), 1))
    return plan


def _stack_init(cfg, key, dtype, kinds: Tuple[str, ...], count: int):
    """vmap the per-layer init over the stack dim."""
    def one(k):
        ks = jax.random.split(k, len(kinds))
        p = {}
        for i, kind in enumerate(kinds):
            bp, _ = block_init(cfg, ks[i], dtype, kind)
            p[f"b{i}"] = bp
        return p
    keys = jax.random.split(key, count)
    params = jax.vmap(one)(keys)
    # logical axes: same per layer, with a leading "layers" axis
    _, ax0 = block_init(cfg, jax.random.PRNGKey(0), dtype, kinds[0])
    ax = {}
    for i, kind in enumerate(kinds):
        _, bx = block_init(cfg, jax.random.PRNGKey(0), dtype, kind)
        ax[f"b{i}"] = jax.tree.map(lambda t: ("layers",) + tuple(t), bx,
                                   is_leaf=lambda t: isinstance(t, tuple))
    return params, ax


def init_params(cfg, key) -> Tuple[Params, Dict]:
    dtype = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4 + len(layer_plan(cfg)))
    p: Params = {"embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                                     dtype)}
    ax: Dict = {"embed": ("vocab", "embed")}
    stacks = []
    stack_axes = []
    for i, (kinds, count) in enumerate(layer_plan(cfg)):
        sp, sax = _stack_init(cfg, ks[2 + i], dtype, kinds, count)
        stacks.append(sp)
        stack_axes.append(sax)
    p["stacks"] = stacks
    ax["stacks"] = stack_axes
    p["final_norm"], ax["final_norm"] = norm_init(cfg, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = embed_init(ks[1], cfg.padded_vocab, cfg.d_model,
                                  dtype).T
        ax["lm_head"] = ("embed", "vocab")
    return p, ax


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def embed_tokens(cfg, p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    x = jnp.take(p["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return with_logical_constraint(x, "batch", None, None)


def unembed(cfg, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return with_logical_constraint(logits, "batch", None, "vocab_act")


def build_inputs(cfg, p: Params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Token embeddings, with the modality-frontend stub prepended (vlm)."""
    x = embed_tokens(cfg, p, batch["tokens"])
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
    return x


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def _scan_stack(cfg, stack_params, x, kinds: Tuple[str, ...], *,
                caches=None, cache_pos=None, collect_cache: bool = False,
                enc_out=None):
    """Scan one homogeneous stack.  Returns (x, new_caches_or_None, aux).

    Decode (``caches`` given) writes the cache in place and copies no
    layer's slab: each layer attends its K/V slice with the new token's row
    selected in as the slice is read, and after the scan one row per layer
    is written into the stacked K/V cache.  SSM states and conv tails, which
    a step rewrites whole, are carried through the scan.  Each Mamba layer
    is handed its stack's whole state with the layer index and hands it
    back with that layer rewritten: on a TPU by one pass of a kernel that
    writes the state where it read it (``ssd.update_state``).  Its conv
    tails are sliced out and written back.  The K/V cache is not carried:
    the TPU compiler lays a carried K/V cache out unlike the donated
    argument and copies it whole into and out of the loop (compiled for a
    described v5e).
    """
    init = (x, jnp.zeros((), jnp.float32))
    if caches is not None:
        read = {b: {n: c for n, c in cache.items() if n not in STATES}
                for b, cache in caches.items()}
        carried = {b: {n: c for n, c in cache.items() if n in STATES}
                   for b, cache in caches.items()}

        def step(carry, xs):
            h, aux, states = carry
            sp, lc, layer = xs
            states, rows = dict(states), {}
            for i, kind in enumerate(kinds):
                b = f"b{i}"
                tails = {n: c for n, c in states[b].items() if n != "state"}
                whole = {n: c for n, c in states[b].items() if n == "state"}
                h, new, a = block_forward(
                    cfg, sp[b], h, kind,
                    cache={**lc[b], **layer_slice(tails, layer), **whole},
                    cache_pos=cache_pos, enc_out=enc_out, layer=layer)
                aux = aux + a
                states[b] = {**write_layer(tails, layer, {
                    n: v for n, v in new.items() if n in tails}),
                    **{n: new[n] for n in whole}}
                rows[b] = {n: v for n, v in new.items() if n not in STATES}
            return (h, aux, states), rows

        count = jax.tree.leaves(stack_params)[0].shape[0]
        (x, aux, states), rows = jax.lax.scan(
            step, init + (carried,), (stack_params, read, jnp.arange(count)))
        return x, {b: {**caches[b], **states[b],
                       **update_cache(cfg, caches[b], rows[b], cache_pos)}
                   for b in caches}, aux

    def body(carry, sp):
        h, aux = carry
        ncs = {}
        for i, kind in enumerate(kinds):
            h, ncs[f"b{i}"], a = block_forward(cfg, sp[f"b{i}"], h, kind,
                                               enc_out=enc_out)
            aux = aux + a
        return (h, aux), (ncs if collect_cache else None)
    (x, aux), ys = jax.lax.scan(jax.checkpoint(body), init, stack_params)
    return x, ys, aux


def forward(cfg, p: Params, batch: Dict[str, jnp.ndarray], *,
            collect_cache: bool = False):
    """Full-sequence forward.  Returns (logits, caches, aux_loss)."""
    x = build_inputs(cfg, p, batch)
    all_caches = []
    aux_total = jnp.zeros((), jnp.float32)
    for stack_params, (kinds, _) in zip(p["stacks"], layer_plan(cfg)):
        x, ys, aux = _scan_stack(cfg, stack_params, x, kinds,
                                 collect_cache=collect_cache)
        aux_total = aux_total + aux
        all_caches.append(ys)
    x = apply_norm(cfg, x, p["final_norm"])
    logits = unembed(cfg, p, x)
    return logits, (all_caches if collect_cache else None), aux_total


def loss_fn(cfg, p: Params, batch: Dict[str, jnp.ndarray]
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token CE (shift-by-one), masking frontend positions for VLMs."""
    logits, _, aux = forward(cfg, p, batch)
    tokens = batch["tokens"]
    if cfg.family == "vlm" and "patch_embeds" in batch:
        n_patch = batch["patch_embeds"].shape[1]
        logits = logits[:, n_patch:, :]
    ce = softmax_cross_entropy(logits[:, :-1, :], tokens[:, 1:],
                               cfg.vocab_size)
    loss = jnp.mean(ce)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux, "ce": loss}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_seq: int) -> List[Any]:
    """Decode cache: one stacked pytree per stack (leading dim = #layers).

    ``decode_step`` writes one K/V row per layer into it, and each layer's
    SSM state and conv tails; in place where its caller donates it, the
    state on a TPU by the kernel that reads it (``ssd.update_state``)."""
    dtype = dtype_of(cfg.param_dtype)
    caches = []
    for kinds, count in layer_plan(cfg):
        def one(_):
            return {f"b{i}": init_block_cache(cfg, kind, batch, max_seq, dtype)
                    for i, kind in enumerate(kinds)}
        caches.append(jax.vmap(one)(jnp.arange(count)))
    return caches


def decode_step(cfg, p: Params, caches: List[Any], token: jnp.ndarray,
                pos: jnp.ndarray):
    """One token for the whole batch: token (B,1) int32, pos () int32.

    Returns (logits (B,1,V), new_caches).
    """
    x = embed_tokens(cfg, p, token)
    new_caches = []
    for stack_params, cache, (kinds, _) in zip(p["stacks"], caches,
                                               layer_plan(cfg)):
        x, ys, _ = _scan_stack(cfg, stack_params, x, kinds,
                               caches=cache, cache_pos=pos)
        new_caches.append(ys)
    x = apply_norm(cfg, x, p["final_norm"])
    logits = unembed(cfg, p, x)
    return logits, new_caches


def prefill(cfg, p: Params, batch: Dict[str, jnp.ndarray]):
    """Process the prompt; returns (last_logits, caches-with-kv)."""
    logits, caches, _ = forward(cfg, p, batch, collect_cache=True)
    return logits[:, -1:, :], caches
