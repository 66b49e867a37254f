"""Per-architecture smoke tests: REDUCED config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs (assignment
requirement (f)); plus a prefill->decode consistency check per family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import reduce_for_smoke
from repro.models import build
from repro.train import AdamWConfig, TrainConfig, init_state, make_train_step


def _batch_for(cfg, B=2, S=32, key=jax.random.PRNGKey(0)):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size - 1)}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, (B, cfg.enc_seq, cfg.frontend_dim), jnp.bfloat16)
    if cfg.family == "vlm":
        batch["tokens"] = batch["tokens"][:, : S - cfg.frontend_seq]
        batch["patch_embeds"] = jax.random.normal(
            key, (B, cfg.frontend_seq, cfg.frontend_dim), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = reduce_for_smoke(get_config(arch))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg)

    logits = bundle.forward(params, batch)
    S_total = batch["tokens"].shape[1] + (cfg.frontend_seq
                                          if cfg.family == "vlm" else 0)
    assert logits.shape == (2, S_total, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    tcfg = TrainConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=1))
    step = jax.jit(make_train_step(bundle.loss, tcfg))
    state = init_state(params, tcfg.opt)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state["step"]) == 1
    # a second step must also be finite (optimizer state exercised)
    state, metrics2 = step(state, batch)
    assert np.isfinite(float(metrics2["loss"]))


@pytest.mark.parametrize("arch", ["llama3_2_1b", "qwen3_4b", "mamba2_1_3b",
                                  "hymba_1_5b", "whisper_large_v3",
                                  "deepseek_moe_16b", "granite_4_0_h_micro"])
def test_prefill_decode_consistency(arch):
    """Next-token logits from (prefill -> decode_step) must match the full
    forward at the same position — the KV-cache/state plumbing invariant."""
    cfg = reduce_for_smoke(get_config(arch))
    # fp32 params keep the comparison tight; high capacity factor removes
    # MoE token drops (capacity-based dropping is context-length dependent,
    # so exact prefill/full equivalence needs the no-drop regime)
    cfg = dataclasses.replace(cfg, param_dtype="float32",
                              compute_dtype="float32",
                              capacity_factor=16.0)
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = _batch_for(cfg, B=B, S=S + 1)
    toks = batch["tokens"]

    # full forward logits at position S-1 predict token S
    full_batch = dict(batch)
    full_batch["tokens"] = toks[:, : S + 1]
    logits_full = bundle.forward(params, full_batch)

    # prefill on first S tokens, then one decode step
    pre_batch = dict(batch)
    pre_batch["tokens"] = toks[:, :S]
    last_logits, caches = bundle.prefill(params, pre_batch)

    n_front = cfg.frontend_seq if cfg.family == "vlm" else 0
    np.testing.assert_allclose(
        np.asarray(last_logits[:, 0, : cfg.vocab_size], np.float32),
        np.asarray(logits_full[:, n_front + S - 1, : cfg.vocab_size],
                   np.float32),
        rtol=2e-3, atol=2e-3)

    # seed a bigger decode cache so position S has a slot
    from repro.serve import seed_decode_cache
    caches = seed_decode_cache(bundle, caches, B, n_front + S + 8)
    dec_logits, _ = bundle.decode(params, caches, toks[:, S:S + 1],
                                  jnp.int32(n_front + S))
    np.testing.assert_allclose(
        np.asarray(dec_logits[:, 0, : cfg.vocab_size], np.float32),
        np.asarray(logits_full[:, n_front + S, : cfg.vocab_size], np.float32),
        rtol=5e-3, atol=5e-3)


def test_param_counts_match_published():
    expected = {
        "qwen2_7b": 7.6e9, "llama3_2_1b": 1.24e9, "qwen2_0_5b": 0.49e9,
        "qwen3_4b": 4.4e9, "mamba2_1_3b": 1.34e9,
        "deepseek_moe_16b": 16.4e9, "llama4_maverick_400b_a17b": 398e9,
        "granite_4_0_h_micro": 3.19e9,
    }
    for arch, want in expected.items():
        got = get_config(arch).param_count()
        assert abs(got - want) / want < 0.12, (arch, got, want)
