"""The decode step writes its cache in place: one K/V row (or SSM state)
per layer into a donated cache.  Served tokens and decode logits must
equal those of a reference decode that writes every layer with a one-hot
select over the whole stacked cache, the write this one replaced."""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import reduce_for_smoke
from repro.launch.mesh import make_test_mesh
from repro.models import build, lm
from repro.models.common import set_activation_rules, set_mesh_context
from repro.parallel import sharding as shd
from repro.serve import EngineConfig, ServeEngine, seed_decode_cache
from repro.serve.engine import Request

BATCH, PROMPT, NEW, MAX_SEQ = 4, 12, 12, 32


def select_update_cache(cfg, cache, rows, pos):
    """Every layer's K/V row written by a one-hot select over the whole
    stacked cache."""
    out = {}
    for n, r in rows.items():
        S = cache[n].shape[2]
        hit = (jnp.arange(S) == (pos % S if cfg.sliding_window else pos)
               )[None, None, :, None]
        out[n] = jnp.where(hit, r.astype(cache[n].dtype), cache[n])
    return out


def select_write_layer(cache, layer, new):
    """A layer's SSM state and conv tails written by a one-hot select."""
    out = dict(cache)
    for n, v in new.items():
        hit = (jnp.arange(cache[n].shape[0]) == layer).reshape(
            (-1,) + (1,) * v.ndim)
        out[n] = jnp.where(hit, v[None].astype(cache[n].dtype), cache[n])
    return out


@contextlib.contextmanager
def select_writes(monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(lm, "update_cache", select_update_cache)
        m.setattr(lm, "write_layer", select_write_layer)
        yield


def small(arch, **kw):
    return dataclasses.replace(reduce_for_smoke(get_config(arch)),
                               param_dtype="float32",
                               compute_dtype="float32",
                               capacity_factor=16.0, **kw)


# (config, meshed): a sliding window of 16 is wrapped by answers that end
# at position 23
CASES = {
    "qwen2": (lambda: small("qwen2_0_5b"), False),
    "qwen2_window_ring": (lambda: small("qwen2_0_5b", sliding_window=16),
                          False),
    "mamba2_ssm": (lambda: small("mamba2_1_3b"), False),
    "hymba_hybrid_window": (lambda: small("hymba_1_5b"), False),
    "whisper_cross": (lambda: small("whisper_large_v3"), False),
    "deepseek_two_stacks": (lambda: small("deepseek_moe_16b"), False),
    "granite_mamba_and_attention_stacks": (
        lambda: small("granite_4_0_h_micro", n_layers=4, layer_kinds=(
            "mamba", "mamba", "dense", "mamba")), False),
    "qwen2_split_kv_mesh": (lambda: small("qwen2_0_5b"), True),
}


def setup(cfg, meshed):
    """(bundle, params, mesh or None), the mesh's context installed."""
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    if not meshed:
        return bundle, params, None
    mesh = make_test_mesh((2, 4), ("data", "model"))
    set_mesh_context(mesh, shd.batch_axes(mesh))
    set_activation_rules(shd.activation_rules(mesh))
    pshard = shd.named_shardings(mesh, shd.param_specs(
        bundle.param_logical_axes(), shd.param_rules(mesh)))
    return bundle, jax.device_put(params, pshard), mesh


def serve(bundle, params, prompts):
    eng = ServeEngine(bundle, params, EngineConfig(batch_size=BATCH,
                                                   max_seq=MAX_SEQ))
    eng.compile(PROMPT)
    reqs = [eng.submit(p, max_new_tokens=NEW) for p in prompts]
    eng.run()
    assert all(len(r.out_tokens) == NEW for r in reqs)
    return eng, np.array([r.out_tokens for r in reqs])


def decode_logits(eng, prompts, tokens, donate):
    """Logits of NEW - 1 decode steps of ``eng``'s model fed the served
    tokens, and the cache they leave."""
    bundle, params = eng.bundle, eng.params
    batch, _ = eng._pad_batch([Request(-1, p) for p in prompts])
    _, caches = jax.jit(bundle.prefill)(params, batch)
    caches = seed_decode_cache(bundle, caches, BATCH, MAX_SEQ)
    step = jax.jit(bundle.decode, donate_argnums=(1,) if donate else ())
    out = []
    for i in range(NEW - 1):
        logits, caches = step(params, caches, jnp.asarray(tokens[:, i:i + 1]),
                              jnp.int32(PROMPT + i))
        out.append(np.asarray(logits, np.float32))
    return np.stack(out), caches


@pytest.mark.parametrize("case", list(CASES))
def test_row_write_serves_what_the_select_write_serves(case, monkeypatch):
    make_cfg, meshed = CASES[case]
    cfg = make_cfg()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size - 1, PROMPT).astype(np.int32)
               for _ in range(BATCH)]
    bundle, params, mesh = setup(cfg, meshed)
    with (jax.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        eng, tokens = serve(bundle, params, prompts)
        logits, caches = decode_logits(eng, prompts, tokens, donate=True)
        with select_writes(monkeypatch):
            ref, ref_tokens = serve(bundle, params, prompts)
            ref_logits, ref_caches = decode_logits(ref, prompts, tokens,
                                                   donate=False)
    np.testing.assert_array_equal(tokens, ref_tokens)
    np.testing.assert_allclose(logits, ref_logits, rtol=1e-5, atol=1e-5)
    for got, want in zip(jax.tree.leaves(caches), jax.tree.leaves(ref_caches)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # one device's share of the cache: a meshed step is compiled per device
    cache_bytes = sum(x.addressable_shards[0].data.nbytes
                      for x in jax.tree.leaves(caches))
    assert eng.stats["decode_aliased_bytes"] == cache_bytes


def test_decode_step_deletes_the_cache_passed_to_it():
    cfg = small("qwen2_0_5b")
    bundle = build(cfg)
    eng = ServeEngine(bundle, bundle.init(jax.random.PRNGKey(0)),
                      EngineConfig(batch_size=2, max_seq=16))
    caches = bundle.init_cache(2, 16)
    tok, out = eng._decode(eng.params, caches, jnp.zeros((2, 1), jnp.int32),
                           np.int32(3))
    assert all(x.is_deleted() for x in jax.tree.leaves(caches))
    assert not any(x.is_deleted() for x in jax.tree.leaves(out))
    assert tok.shape == (2, 1)
