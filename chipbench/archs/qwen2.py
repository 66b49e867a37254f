"""Qwen2: a dense GQA decoder with q/k/v biases, SwiGLU and RMSNorm
(``reference.py``, ``work.Shape``)."""
from __future__ import annotations

import dataclasses

import reference
import work


def program_config(model: dict):
    """The program's config for a configuration file, every published size
    set from the file."""
    from repro.configs import get_config
    d, h = model["hidden_size"], model["num_attention_heads"]
    cfg = dataclasses.replace(
        get_config(model["program"]["arch"]),
        n_layers=model["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim", d // h),
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        rope_theta=float(model["rope_theta"]),
        rms_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        qkv_bias=bool(model["program"]["qkv_bias"]),
        sliding_window=(model["sliding_window"]
                        if model["use_sliding_window"] else 0),
        param_dtype=model["torch_dtype"], compute_dtype=model["torch_dtype"])
    if (cfg.family, cfg.mlp, cfg.norm, cfg.qk_norm) != (
            "dense", "swiglu", "rmsnorm", False):
        raise ValueError(f"{cfg.name}: not a Qwen2-style dense decoder")
    return cfg


def seed_leaves(params: dict, model: dict, seed: int) -> None:
    """The reference's seeded q/k/v biases and norm scales, in place of the
    initializer's zeros and ones."""
    (stack,) = params["stacks"]
    block = stack["b0"]
    x = reference.norms_and_biases(model, seed)
    leaves = {("attn", "bq"): x["bq"], ("attn", "bk"): x["bk"],
              ("attn", "bv"): x["bv"], ("ln1", "scale"): x["ln1"],
              ("ln2", "scale"): x["ln2"]}
    for (group, name), value in leaves.items():
        old = block[group][name]
        if old.shape != value.shape or old.dtype != value.dtype:
            raise ValueError(f"{group}.{name}: program {old.shape} "
                             f"{old.dtype}, reference {value.shape} "
                             f"{value.dtype}")
        block[group][name] = value
    params["final_norm"]["scale"] = x["final"]


def shape(model: dict) -> work.Shape:
    return work.Shape.of(model)


served_gaps = reference.served_gaps
