"""granite_4_0_h_micro through the harness on the CPU: at the published
widths, four layers of both kinds ([mamba, mamba, attention, mamba]) and a
cut vocabulary.  The module is found by ``model_type``, the reference
draws the weights that are served, the program agrees with it where both
compute in float32, the seeded model's answers follow the prompt, and
``correct`` comes out false for each fault of the architecture's own."""
import copy
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import archs
import granite_reference as reference
import harness
from conftest import BENCH, tiny_traffic

SEED = 2**31 + 21
NAME = "granite_4_0_h_micro-chat-open"
TINY_SIZES = {"num_hidden_layers": 4, "vocab_size": 4096,
              "layer_types": ["mamba", "mamba", "attention", "mamba"]}
# whole runs judged against the configuration's limit: eight layers, since
# a dropped dt_bias moves the logits of four by about as much as the limit
# (widest gaps 0.030 in four layers, 0.039 in eight, on the CPU at SEED)
JUDGED_SIZES = {"num_hidden_layers": 8,
                "layer_types": TINY_SIZES["layer_types"] * 2}


def published() -> dict:
    return json.loads((BENCH / "configs" / "granite_4_0_h_micro.json"
                       ).read_text())


def tiny_model(**overrides) -> dict:
    model = published()
    model.update(TINY_SIZES, **overrides)
    return model


def tiny_cell(**overrides):
    """The granite chat cell's metrics on a tiny model."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = harness.Cell.load(spec, NAME)
    return harness.Cell(name=NAME, chips=1, model=tiny_model(**overrides),
                        traffic=tiny_traffic(),
                        end_to_end=copy.deepcopy(cell.end_to_end),
                        per_layer=copy.deepcopy(cell.per_layer))


def run(cell, devices):
    return harness.run_cell(cell, SEED, 1.0, devices[:1],
                            time.perf_counter())


def test_the_module_is_found_and_serves_every_published_size():
    model = published()
    mod = archs.for_model(model)
    assert mod.__name__.endswith("granitemoehybrid")
    cfg = mod.program_config(model)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim_, cfg.d_ff, cfg.vocab_size) == (
        40, 2048, 32, 8, 64, 8192, 100352)
    assert (cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state,
            cfg.ssm_conv_width, cfg.ssm_chunk) == (64, 64, 128, 4, 256)
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "dense"] == \
        [5, 15, 25, 35]
    assert not cfg.rope
    assert model["reduced"] == []
    with pytest.raises(ValueError, match="Granite"):
        mod.program_config(dict(model, num_local_experts=72))
    with pytest.raises(ValueError, match="Granite"):
        mod.program_config(dict(model, mamba_conv_bias=False))


def test_work_counts_by_hand():
    s = archs.for_model(published()).shape(published())
    # in-projection 2048 x (4096 z + 4352 xBC + 64 dt), out 4096 x 2048
    assert s.mamba_matmul_params == 25_821_184
    # q and o 2048 x 2048, k and v 2048 x 512
    assert s.attention_matmul_params == 10_485_760
    # 36 x (2 x 25,821,184 + 2 x 4 x 4352 + 5 x 524,288)
    # + 4 x 2 x 10,485,760 + 40 x 2 x 50,331,648
    assert s.token_flops == 6_065_168_384
    # 36 x 2 x 524,288 x 4 bytes of state, 36 x 2 x 3 x 4352 x 2 of tails
    # read and written; 4 layers x 2 x 8 x 64 x 2 bytes per position
    assert s.state_bytes == 76_437_504
    assert s.decode_slot_bytes(1099) == 4_096 + 1101 * 8_192 + 152_875_008
    assert s.decode_flops(1099) == (6_065_168_384 + 4 * 4 * 32 * 64 * 1100
                                    + 2 * 2048 * 100352)
    # about 3.19 B parameters in bfloat16
    assert 6.35e9 < s.weight_bytes() < 6.40e9


def test_the_reference_draws_the_weights_the_harness_serves():
    cell = tiny_cell()
    model = cell.model
    params = harness.build_engine(cell, SEED).params
    ek, keys = reference.layer_keys(model, SEED)
    m = reference._dims(model)
    mt = tuple(sorted(m.items()))
    emb, _ = reference._head_weights(ek, mt)
    np.testing.assert_array_equal(emb, params["embed"][: m["V"]])
    x = reference.extra_leaves(model, SEED)
    layers = {0: (params["stacks"][0], 0), 1: (params["stacks"][0], 1),
              2: (params["stacks"][1], 0), 3: (params["stacks"][2], 0)}
    mamba = {0: 0, 1: 1, 3: 2}
    for li, (stack, j) in layers.items():
        kind = model["layer_types"][li]
        block = stack["b0"]
        w = reference._layer_weights(keys[li], mt, kind)
        group = "attn" if kind == "attention" else "ssd"
        for name, want in w.items():
            where = "mlp" if name in ("wg", "wu", "wd") else group
            np.testing.assert_array_equal(want, block[where][name][j])
        np.testing.assert_array_equal(x["ln1"][li], block["ln1"]["scale"][j])
        np.testing.assert_array_equal(x["ln2"][li], block["ln2"]["scale"][j])
        if kind == "mamba":
            for name in ("A_log", "dt_bias", "D", "conv_x_bias",
                         "conv_BC_bias", "norm"):
                np.testing.assert_array_equal(x[name][mamba[li]],
                                              block["ssd"][name][j])
    np.testing.assert_array_equal(x["final"], params["final_norm"]["scale"])
    a = np.exp(np.asarray(x["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(x["dt_bias"])))
    assert 1e-3 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    assert np.std(np.asarray(x["conv_x_bias"], np.float32)) > 0.04


def test_float32_program_serves_the_references_greedy_tokens(cpu_devices):
    """Prefill, then decode through the donated cache in ServeEngine,
    against the reference's full forward over the served tokens."""
    cell = tiny_cell(torch_dtype="float32")
    with jax.default_matmul_precision("highest"):
        run_, _, _ = harness.serve_window(cell, SEED, 1.0, cpu_devices[:1],
                                          time.perf_counter())
    c = harness.check(cell, SEED, run_.served, control=True)
    assert c["tokens_checked"] > 20
    assert c["max_logit_gap"] < 1e-5
    assert c["control_max_logit_gap"] > 100 * max(c["max_logit_gap"], 1e-7)
    # cache_mb_per_slot: every slot's K/V and recurrent state, in MB
    m = reference.Shape.of(cell.model)
    cache = run_.stats["cache_bytes"]
    assert cache["kv"] == 2 * m.attention_layers * m.kv_heads * m.head_dim \
        * cell.traffic["max_seq"] * cell.traffic["batch_size"] * 4
    assert cache["state"] > cache["kv"] > 0
    assert harness.read_metric("cache_mb_per_slot", run_) == pytest.approx(
        (cache["kv"] + cache["state"]) / cell.traffic["batch_size"] / 1e6)
    del run_.stats["cache_bytes"]       # an engine that does not count them
    assert harness.read_metric("cache_mb_per_slot", run_) is None


def test_answers_follow_the_prompt():
    """256 random prompts: the reference puts at least 50 distinct tokens
    first, so the seeded leaves do not make every answer one token."""
    model = tiny_model()
    toks = np.random.default_rng(3).integers(
        0, model["vocab_size"], (256, 8)).astype(np.int32)
    top = reference.logits(model, SEED, toks)[:, -1].argmax(-1)
    assert len(set(top.tolist())) >= 50


def test_a_sound_run_is_correct(cpu_devices):
    res = run(tiny_cell(**JUDGED_SIZES), cpu_devices)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 4
    assert res["info"]["tokens_checked"] > 20


def _drop_conv_bias(monkeypatch):
    from repro.models import ssd
    conv, step = ssd._causal_conv, ssd._conv_step
    monkeypatch.setattr(ssd, "_causal_conv",
                        lambda x, w, b: conv(x, w, jnp.zeros_like(b)))
    monkeypatch.setattr(ssd, "_conv_step",
                        lambda buf, xt, w, b: step(buf, xt, w,
                                                   jnp.zeros_like(b)))


def _drop_ssd_leaf(name, value):
    def fault(monkeypatch):
        from repro.models import blocks, ssd
        fwd, dec = ssd.ssd_forward, ssd.ssd_decode_step

        def without(p):
            return {**p, name: jnp.full_like(p[name], value)}
        monkeypatch.setattr(blocks, "ssd_forward",
                            lambda cfg, p, *a, **k: fwd(cfg, without(p),
                                                        *a, **k))
        monkeypatch.setattr(blocks, "ssd_decode_step",
                            lambda cfg, p, *a, **k: dec(cfg, without(p),
                                                        *a, **k))
    return fault


def _config_fault(**change):
    def fault(monkeypatch):
        import dataclasses
        mod = archs.for_model(published())
        make = mod.program_config
        monkeypatch.setattr(mod, "program_config",
                            lambda model: dataclasses.replace(make(model),
                                                              **change))
    return fault


FAULTS = {
    "conv_bias_dropped": _drop_conv_bias,
    "D_dropped": _drop_ssd_leaf("D", 0.0),
    "dt_bias_dropped": _drop_ssd_leaf("dt_bias", 0.0),
    "residual_multiplier_1": _config_fault(residual_multiplier=1.0),
    "rope_on_attention": _config_fault(rope=True),
    "scale_one_over_sqrt_head_dim": _config_fault(
        attention_multiplier=1.0 / math.sqrt(64)),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_fault_of_the_architecture_is_not_correct(cpu_devices, monkeypatch,
                                                    fault):
    FAULTS[fault](monkeypatch)
    res = run(tiny_cell(**JUDGED_SIZES), cpu_devices)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]
