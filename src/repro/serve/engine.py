"""Batched serving engine: continuous-batching prefill/decode on a virtual
NPU submesh.

Requests queue up, get micro-batched into a fixed-size decode batch
(padding with idle slots), prefill seeds each slot's KV cache, and a single
jit'd decode step advances every active slot one token per tick — the
standard orchestration loop of an LLM server.  The same loop runs on the
CPU for the examples and tests and on a TPU (``chip_smoke.py``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.common import get_mesh_context
from ..parallel import sharding as shd


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 16
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class EngineConfig:
    batch_size: int = 4
    max_seq: int = 256
    greedy: bool = True


def seed_decode_cache(bundle, prefill_caches, batch_size: int, max_seq: int):
    """Copy prefill K/V (length S) into fresh max_seq decode caches.

    For sliding-window rings this is exact while prompt_len <= window (ring
    slot i == absolute position i); longer prompts re-wrap consistently with
    update_cache's pos % S indexing.  SSM states/conv tails pass through
    unchanged (no sequence dim).
    """
    caches = bundle.init_cache(batch_size, max_seq)

    def seed(dst, src):
        if src is None:
            return dst
        if src.shape == dst.shape:
            return src
        if dst.ndim >= 4 and src.ndim == dst.ndim and \
                src.shape[2] != dst.shape[2]:
            n = min(src.shape[2], dst.shape[2])
            return dst.at[:, :, :n].set(src[:, :, src.shape[2] - n:])
        return dst

    out = []
    for dst_stack, src_stack in zip(caches, prefill_caches):
        if src_stack is None:
            out.append(dst_stack)
        else:
            out.append(jax.tree.map(seed, dst_stack, src_stack))
    return out


class ServeEngine:
    """Single-host engine over a ModelBundle (works meshed or unmeshed).

    Prefill and decode are each one jitted step that ends in the greedy
    token.  Under an installed mesh context (``set_mesh_context``) the
    steps pin the decode cache to its split-KV sharding and the token to
    replicated, so every decode step reuses one compiled program.
    """

    def __init__(self, bundle, params, ecfg: EngineConfig):
        self.bundle = bundle
        self.params = params
        self.ecfg = ecfg
        self.cfg = bundle.cfg
        self._shardings = self._step_shardings()
        self._decode = jax.jit(self._decode_step)
        self._prefill = jax.jit(self._prefill_step)
        self.queue: List[Request] = []
        # prefill_s / decode_s: host wall time of the device work, each
        # window closed by a device sync
        self.stats: Dict[str, float] = {"prefills": 0, "decode_steps": 0,
                                        "tokens_out": 0, "prefill_s": 0.0,
                                        "decode_s": 0.0}

    def _step_shardings(self):
        mesh = get_mesh_context()[0]
        if mesh is None:
            return None
        shapes = jax.eval_shape(lambda: self.bundle.init_cache(
            self.ecfg.batch_size, self.ecfg.max_seq))
        return (NamedSharding(mesh, P()),
                shd.named_shardings(mesh, shd.cache_specs(shapes, mesh)))

    def _finish_step(self, logits, caches):
        tok = jnp.argmax(logits[..., : self.cfg.vocab_size],
                         axis=-1).astype(jnp.int32)
        if self._shardings is not None:
            tok, caches = jax.lax.with_sharding_constraint(
                (tok, caches), self._shardings)
        return tok, caches

    def _prefill_step(self, params, batch):
        last_logits, caches = self.bundle.prefill(params, batch)
        caches = seed_decode_cache(self.bundle, caches, self.ecfg.batch_size,
                                   self.ecfg.max_seq)
        return self._finish_step(last_logits, caches)

    def _decode_step(self, params, caches, tok, pos):
        logits, caches = self.bundle.decode(params, caches, tok, pos)
        return self._finish_step(logits, caches)

    def compile(self, prompt_len: int) -> Dict[str, float]:
        """Compile prefill and decode ahead of serving prompts of
        ``prompt_len`` tokens; returns the compile seconds of each."""
        batch = jax.eval_shape(
            lambda: self._pad_batch([Request(-1, np.zeros(prompt_len,
                                                          np.int32))])[0])
        t0 = time.perf_counter()
        prefill = self._prefill.lower(self.params, batch).compile()
        out = {"prefill_s": time.perf_counter() - t0}
        # decode takes prefill's outputs: pinned under a mesh, else
        # uncommitted (no sharding)
        tok, caches = prefill.out_info
        if self._shardings is None:
            tok, caches = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                (tok, caches))
        t0 = time.perf_counter()
        self._decode.lower(self.params, caches, tok,
                           jax.ShapeDtypeStruct((), jnp.int32)).compile()
        out["decode_s"] = time.perf_counter() - t0
        return out

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> Request:
        # the last new token is sampled, never written to the cache
        if len(prompt) + max_new_tokens - 1 > self.ecfg.max_seq:
            raise ValueError(
                f"prompt {len(prompt)} + {max_new_tokens} new tokens "
                f"overrun max_seq {self.ecfg.max_seq}")
        req = Request(rid=len(self.queue), prompt=np.asarray(prompt),
                      max_new_tokens=max_new_tokens)
        self.queue.append(req)
        return req

    # -- batch plumbing ------------------------------------------------------
    def _pad_batch(self, reqs: List[Request]) -> Dict[str, jnp.ndarray]:
        B = self.ecfg.batch_size
        S = max(len(r.prompt) for r in reqs)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - len(r.prompt):] = r.prompt  # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (B, self.cfg.frontend_seq, self.cfg.frontend_dim),
                jnp.bfloat16)
        if self.cfg.family == "encdec":
            batch["frames"] = jnp.zeros(
                (B, self.cfg.enc_seq, self.cfg.frontend_dim), jnp.bfloat16)
        return batch, S

    # -- main loop -----------------------------------------------------------
    def run(self, max_ticks: int = 64) -> List[Request]:
        """Process the queue to completion (or tick budget)."""
        pending = [r for r in self.queue if not r.done]
        while pending and max_ticks > 0:
            reqs = pending[: self.ecfg.batch_size]
            batch, S = self._pad_batch(reqs)
            t0 = time.perf_counter()
            tok, caches = self._prefill(self.params, batch)
            jax.block_until_ready((tok, caches))
            self.stats["prefill_s"] += time.perf_counter() - t0
            self.stats["prefills"] += 1
            host_tok = np.asarray(tok)
            for i, r in enumerate(reqs):
                r.out_tokens.append(int(host_tok[i, 0]))
                self.stats["tokens_out"] += 1
            pos = S
            steps = max(r.max_new_tokens for r in reqs) - 1
            t0 = time.perf_counter()
            for _ in range(min(steps, max_ticks)):
                tok, caches = self._decode(self.params, caches, tok,
                                           np.int32(pos))
                host_tok = np.asarray(tok)
                self.stats["decode_steps"] += 1
                for i, r in enumerate(reqs):
                    if len(r.out_tokens) < r.max_new_tokens:
                        r.out_tokens.append(int(host_tok[i, 0]))
                        self.stats["tokens_out"] += 1
                pos += 1
                max_ticks -= 1
            self.stats["decode_s"] += time.perf_counter() - t0
            for r in reqs:
                r.done = True
            pending = [r for r in self.queue if not r.done]
        return self.queue
