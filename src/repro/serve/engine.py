"""Batched serving engine: continuous-batching prefill/decode on a virtual
NPU submesh.

Requests queue up, get micro-batched into a fixed-size decode batch
(padding with idle slots), prefill seeds each slot's KV cache, and a single
jit'd decode step advances every active slot one token per tick — the
standard orchestration loop of an LLM server.  The same loop runs on the
CPU for the examples and tests and on a TPU (``chip_smoke.py``).

The loop is always traced: it opens a short ``jax.profiler.TraceAnnotation``
span (``serve.*``, with the batch's id among its args) around each piece of
host work, which a profiler session records on the device trace's clock
and which costs about a microsecond without one.  ``ServeEngine.stats``
counts requests admitted and compiles of the two steps, and each
:class:`Request` carries the host clock of its submission and admission.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels.ssd_state import KERNEL as STATE_KERNEL
from ..models.common import get_mesh_context
from ..models.lm import STATES
from ..parallel import sharding as shd

# host spans of the serving loop.  Each is opened and closed inside one
# step, so that none is open when a profile starts (a span opened before
# the profiler starts is never recorded).
SPAN_ADMIT = "serve.admit"        # batch, n, rid0, rid1
SPAN_PREFILL = "serve.prefill"    # batch, prompt_len
SPAN_DISPATCH = "serve.dispatch"  # batch, pos, live
SPAN_READ = "serve.read"          # batch
SPAN_EMIT = "serve.emit"          # batch, n

# compiles (or compile-cache loads) of the two steps, as JAX reports them;
# a compile runs in the thread that called the step, so each thread counts
# its own and an engine adds what its calls added
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
STEP_FUNS = frozenset({"jit(_prefill_step)", "jit(_decode_step)"})
_compiles = threading.local()


def _thread_compiles() -> int:
    return getattr(_compiles, "n", 0)


def _count_compile(event: str, duration_s: float, **kwargs) -> None:
    if event == COMPILE_EVENT and kwargs.get("fun_name") in STEP_FUNS:
        _compiles.n = _thread_compiles() + 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 16
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # host clock (perf_counter) at ``submit`` and when its batch was formed
    submitted_at: Optional[float] = None
    admitted_at: Optional[float] = None


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


def cache_bytes(caches) -> Dict[str, int]:
    """Bytes of a decode cache (arrays or shapes): its K/V leaves (``kv``)
    and the leaves a decode step rewrites whole (``state``: SSM states and
    conv tails)."""
    out = {"kv": 0, "state": 0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
        kind = "state" if getattr(path[-1], "key", None) in STATES else "kv"
        out[kind] += math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
    return out


def state_inplace_layers(hlo: str, caches) -> int:
    """Mamba layers a compiled decode step (its HLO text ``hlo``) updates by
    the in-place state kernel: the layers of every stacked SSM state in the
    cache where the step calls the kernel, else 0 (``ssd.update_state``
    makes one choice for the whole step)."""
    if not any("tpu_custom_call" in line and STATE_KERNEL in line
               for line in hlo.splitlines()):
        return 0
    return sum(leaf.shape[0]
               for path, leaf in jax.tree_util.tree_leaves_with_path(caches)
               if getattr(path[-1], "key", None) == "state")


class ServeEngine:
    """Single-host engine over a ModelBundle (works meshed or unmeshed).

    Prefill and decode are each one jitted step that ends in the greedy
    token.  The decode step takes its cache donated: it writes each layer's
    new K/V row into the cache in place and hands the same buffers back, so
    the cache passed to it is deleted.  Under an installed mesh context
    (``set_mesh_context``) the steps pin the decode cache to its split-KV
    sharding and the token to replicated, so every decode step reuses one
    compiled program.
    """

    def __init__(self, bundle, params, ecfg: EngineConfig):
        self.bundle = bundle
        self.params = params
        self.ecfg = ecfg
        self.cfg = bundle.cfg
        self._shardings = self._step_shardings()
        self._decode = jax.jit(self._decode_step, donate_argnums=(1,))
        self._prefill = jax.jit(self._prefill_step)
        self.queue: List[Request] = []
        # prefill_s / decode_s: host wall time of the device work, each
        # window closed by a device sync; tokens_out counts each request's
        # prefill token, so (tokens_out - admitted) / (decode_steps x
        # batch_size) is the share of decode slots that emitted a token;
        # compiles: compiles (or compile-cache loads) of the two steps;
        # decode_aliased_bytes: the compiled decode step's output bytes that
        # reuse its donated input (the whole cache when the donation takes),
        # set by ``compile`` (None where the backend does not report it);
        # cache_bytes: the decode cache's bytes, {"kv": K/V leaves,
        # "state": the leaves a step rewrites whole}, set by ``compile``;
        # state_inplace_layers: the Mamba layers whose state the compiled
        # decode step updates in place by the Pallas kernel, in one pass
        # over HBM (0 off the TPU, under a mesh, or with no SSM state), set
        # by ``compile``
        self.stats: Dict[str, float] = {"prefills": 0, "decode_steps": 0,
                                        "tokens_out": 0, "admitted": 0,
                                        "compiles": 0, "prefill_s": 0.0,
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
        compiles0 = _thread_compiles()
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
        decode = self._decode.lower(self.params, caches, tok,
                                    jax.ShapeDtypeStruct((), jnp.int32)
                                    ).compile()
        out["decode_s"] = time.perf_counter() - t0
        self.stats["cache_bytes"] = cache_bytes(caches)
        mem = decode.memory_analysis()
        self.stats["decode_aliased_bytes"] = getattr(
            mem, "alias_size_in_bytes", None)
        self.stats["state_inplace_layers"] = state_inplace_layers(
            decode.as_text(), caches)
        self.stats["compiles"] += _thread_compiles() - compiles0
        return out

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16) -> Request:
        # the last new token is sampled, never written to the cache
        if len(prompt) + max_new_tokens - 1 > self.ecfg.max_seq:
            raise ValueError(
                f"prompt {len(prompt)} + {max_new_tokens} new tokens "
                f"overrun max_seq {self.ecfg.max_seq}")
        req = Request(rid=len(self.queue), prompt=np.asarray(prompt),
                      max_new_tokens=max_new_tokens,
                      submitted_at=time.perf_counter())
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
        compiles0 = _thread_compiles()
        pending = [r for r in self.queue if not r.done]
        while pending and max_ticks > 0:
            max_ticks -= self._serve_batch(pending[: self.ecfg.batch_size],
                                           max_ticks)
            pending = [r for r in self.queue if not r.done]
        self.stats["compiles"] += _thread_compiles() - compiles0
        return self.queue

    def _serve_batch(self, reqs: List[Request], max_ticks: int) -> int:
        """Prefill ``reqs`` as one batch, then decode it for at most
        ``max_ticks`` steps; returns the steps taken."""
        b = self.stats["prefills"]          # the batch's id: batches before it
        with jax.profiler.TraceAnnotation(SPAN_ADMIT, batch=b, n=len(reqs),
                                          rid0=reqs[0].rid,
                                          rid1=reqs[-1].rid):
            now = time.perf_counter()
            for r in reqs:
                r.admitted_at = now
            self.stats["admitted"] += len(reqs)
            batch, S = self._pad_batch(reqs)
        with jax.profiler.TraceAnnotation(SPAN_PREFILL, batch=b,
                                          prompt_len=S):
            t0 = time.perf_counter()
            tok, caches = self._prefill(self.params, batch)
            jax.block_until_ready((tok, caches))
            self.stats["prefill_s"] += time.perf_counter() - t0
        self.stats["prefills"] += 1
        with jax.profiler.TraceAnnotation(SPAN_READ, batch=b):
            host_tok = np.asarray(tok)
        with jax.profiler.TraceAnnotation(SPAN_EMIT, batch=b, n=len(reqs)):
            for i, r in enumerate(reqs):
                r.out_tokens.append(int(host_tok[i, 0]))
            self.stats["tokens_out"] += len(reqs)
        # slots still short of their max_new_tokens
        live = sum(len(r.out_tokens) < r.max_new_tokens for r in reqs)
        pos = S
        steps = max(0, min(max(r.max_new_tokens for r in reqs) - 1,
                           max_ticks))
        t0 = time.perf_counter()
        for _ in range(steps):
            with jax.profiler.TraceAnnotation(SPAN_DISPATCH, batch=b,
                                              pos=pos, live=live):
                tok, caches = self._decode(self.params, caches, tok,
                                           np.int32(pos))
            with jax.profiler.TraceAnnotation(SPAN_READ, batch=b):
                host_tok = np.asarray(tok)
            self.stats["decode_steps"] += 1
            with jax.profiler.TraceAnnotation(SPAN_EMIT, batch=b, n=live):
                self.stats["tokens_out"] += live
                live = 0
                for i, r in enumerate(reqs):
                    if len(r.out_tokens) < r.max_new_tokens:
                        r.out_tokens.append(int(host_tok[i, 0]))
                        live += len(r.out_tokens) < r.max_new_tokens
            pos += 1
        self.stats["decode_s"] += time.perf_counter() - t0
        for r in reqs:
            r.done = True
        return steps
