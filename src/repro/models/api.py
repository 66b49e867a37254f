"""Uniform model API: one bundle per architecture config.

``build(cfg)`` returns a :class:`ModelBundle` whose members are plain
functions — the launcher, trainer, server and tests all consume this one
interface:

    init(key)                      -> params
    loss(params, batch)            -> (scalar, metrics)
    forward(params, batch)         -> logits
    prefill(params, batch)         -> (last_logits, caches)
    decode(params, caches, token, pos) -> (logits, caches)
    init_cache(batch, max_seq)     -> caches
    param_logical_axes()           -> pytree of logical-axis tuples
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax

from . import lm, whisper
from ..configs.base import ModelConfig


@dataclasses.dataclass
class ModelBundle:
    cfg: ModelConfig
    init: Callable
    loss: Callable
    forward: Callable
    prefill: Callable
    decode: Callable
    init_cache: Callable
    param_logical_axes: Callable


def build(cfg: ModelConfig) -> ModelBundle:
    mod = whisper if cfg.family == "encdec" else lm

    def init(key):
        p, _ = mod.init_params(cfg, key)
        return p

    def param_logical_axes():
        cell = {}

        def f(k):
            p, ax = mod.init_params(cfg, k)
            cell["ax"] = ax  # static metadata; params never materialize
            return p

        jax.eval_shape(f, jax.random.PRNGKey(0))
        return cell["ax"]

    def loss(params, batch):
        return mod.loss_fn(cfg, params, batch)

    def forward(params, batch):
        out = mod.forward(cfg, params, batch)
        return out[0]

    def prefill(params, batch):
        return mod.prefill(cfg, params, batch)

    def decode(params, caches, token, pos):
        return mod.decode_step(cfg, params, caches, token, pos)

    def init_cache(batch, max_seq):
        return mod.init_cache(cfg, batch, max_seq)

    return ModelBundle(cfg=cfg, init=init, loss=loss, forward=forward,
                       prefill=prefill, decode=decode, init_cache=init_cache,
                       param_logical_axes=param_logical_axes)
