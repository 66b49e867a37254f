"""granite-4.0-h-micro: Mamba-2 and NoPE attention layers by a per-layer
kind list, muP multipliers and conv biases.

At a small width with both layer kinds, in float32, the program's prefill
logits and what ``ServeEngine`` serves through prefill and the donated
decode cache agree with the plain reference of ``chipbench/
granite_reference.py`` (a sequential Mamba-2 recurrence, independent of the
program's chunked scan), with the leaves the initializer leaves constant
drawn from the seed as the benchmark draws them."""
import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import reduce_for_smoke
from repro.models import build
from repro.models.lm import layer_plan
from repro.serve import EngineConfig, ServeEngine

ROOT = Path(__file__).resolve().parents[1]
SEED = 11
KINDS = ["mamba", "mamba", "attention", "mamba"]
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "shared_intermediate_size": 128,
         "mamba_d_head": 8, "mamba_n_heads": 16, "mamba_d_state": 8,
         "mamba_chunk_size": 8, "vocab_size": 512, "layer_types": KINDS,
         "num_hidden_layers": len(KINDS), "torch_dtype": "float32"}


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod             # its dataclass looks itself up
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _load("granite_reference", "chipbench/granite_reference.py")


@pytest.fixture(scope="module")
def arch(reference):
    return _load("granitemoehybrid", "chipbench/archs/granitemoehybrid.py")


def small_model() -> dict:
    model = json.loads((ROOT / "chipbench" / "configs"
                        / "granite_4_0_h_micro.json").read_text())
    model.update(SMALL)
    return model


def program(arch, model):
    """The program's config for ``model``, weights from SEED and the
    reference's seeded leaves in them, as the benchmark serves them."""
    cfg = arch.program_config(model)
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(SEED))
    arch.seed_leaves(params, model, SEED)
    return cfg, bundle, params


def test_layer_plan_follows_the_layer_kinds():
    granite = get_config("granite_4_0_h_micro")
    period = ("mamba",) * 5 + ("dense",) + ("mamba",) * 4
    assert layer_plan(granite) == [(period, 4)]
    assert granite.param_count() == pytest.approx(3.19e9, rel=0.01)
    runs = dataclasses.replace(granite, layer_kinds=(
        "mamba", "mamba", "dense", "mamba"), n_layers=4)
    assert layer_plan(runs) == [(("mamba",), 2), (("dense",), 1),
                                (("mamba",), 1)]
    assert layer_plan(get_config("qwen2_0_5b")) == [(("dense",), 24)]
    assert layer_plan(get_config("deepseek_moe_16b")) == [
        (("dense",), 1), (("moe",), 27)]
    assert layer_plan(get_config("llama4_maverick_400b_a17b")) == [
        (("dense", "moe"), 24)]


def test_prefill_logits_match_the_reference(reference, arch):
    model = small_model()
    cfg, bundle, params = program(arch, model)
    toks = np.random.default_rng(0).integers(0, 512, (3, 20)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        got = bundle.forward(params, {"tokens": jnp.asarray(toks)})
    want = reference.logits(model, SEED, toks)
    np.testing.assert_allclose(np.asarray(got)[..., :512], want,
                               rtol=1e-4, atol=1e-5)


def test_served_tokens_match_the_reference(reference, arch):
    """Prefill, then decoding through the donated cache, against the
    reference's full forward over the prompt and the served tokens."""
    model = small_model()
    cfg, bundle, params = program(arch, model)
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, 512, (4, 12)).astype(np.int32)
    eng = ServeEngine(bundle, params, EngineConfig(batch_size=4, max_seq=32))
    with jax.default_matmul_precision("highest"):
        eng.compile(12)
        reqs = [eng.submit(p, max_new_tokens=16) for p in prompts]
        eng.run()
    served = np.array([r.out_tokens for r in reqs])
    seq = np.concatenate([prompts, served[:, :-1]], 1)
    want = reference.logits(model, SEED, seq)[:, 11:]
    np.testing.assert_array_equal(served, want.argmax(-1))
    assert len(np.unique(served)) > 8       # answers are not one token


def test_cache_bytes_and_the_donation_cover_kv_and_state():
    cfg = dataclasses.replace(reduce_for_smoke(
        get_config("granite_4_0_h_micro")), layer_kinds=(
        "mamba", "mamba", "dense", "mamba"), n_layers=4)
    bundle = build(cfg)
    eng = ServeEngine(bundle, bundle.init(jax.random.PRNGKey(0)),
                      EngineConfig(batch_size=2, max_seq=24))
    eng.compile(8)
    caches = jax.eval_shape(lambda: bundle.init_cache(2, 24))
    kv = state = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
        n = leaf.size * leaf.dtype.itemsize
        if path[-1].key in ("k", "v"):
            kv += n
        else:
            state += n
    # 1 attention layer of K and V; 3 Mamba layers of state and conv tails
    # (bfloat16 K/V and conv tails, float32 state)
    assert kv == 2 * 2 * 24 * 2 * 16 * 2
    assert state == 3 * 2 * (16 * 8 * 8 * 4 + 3 * (128 + 16) * 2)
    assert eng.stats["cache_bytes"] == {"kv": kv, "state": state}
    assert eng.stats["decode_aliased_bytes"] == kv + state
