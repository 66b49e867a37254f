"""Granite-4.0-H: Mamba-2 layers and NoPE GQA layers, each followed by a
SwiGLU MLP, with muP multipliers (``granite_reference.py``)."""
from __future__ import annotations

import dataclasses

import granite_reference as reference

MAMBA_LEAVES = ("A_log", "dt_bias", "D", "conv_x_bias", "conv_BC_bias",
                "norm")


def program_config(model: dict):
    """The program's config for a configuration file, every published size
    set from the file."""
    from repro.configs import get_config
    d, h = model["hidden_size"], model["num_attention_heads"]
    if (model["mamba_n_groups"], model["num_local_experts"],
            model["mamba_proj_bias"], model["mamba_conv_bias"],
            model["attention_bias"], model["position_embedding_type"],
            model["hidden_act"]) != (
            1, 0, False, True, False, "nope", "silu") or \
            model["mamba_n_heads"] * model["mamba_d_head"] != \
            model["mamba_expand"] * d:
        raise ValueError("not a dense Granite-4.0-H: one group, no experts, "
                         "conv biases and no projection biases, NoPE, SiLU")
    return dataclasses.replace(
        get_config(model["program"]["arch"]),
        n_layers=model["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=model["num_key_value_heads"], head_dim=d // h,
        d_ff=model["shared_intermediate_size"],
        vocab_size=model["vocab_size"], rms_eps=float(model["rms_norm_eps"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        ssm_state=model["mamba_d_state"], ssm_expand=model["mamba_expand"],
        ssm_headdim=model["mamba_d_head"], ssm_conv_width=model["mamba_d_conv"],
        ssm_chunk=model["mamba_chunk_size"],
        layer_kinds=tuple("dense" if k == "attention" else k
                          for k in reference.kinds_of(model)),
        rope=False,
        embedding_multiplier=float(model["embedding_multiplier"]),
        residual_multiplier=float(model["residual_multiplier"]),
        attention_multiplier=float(model["attention_multiplier"]),
        logits_scaling=float(model["logits_scaling"]),
        param_dtype=model["torch_dtype"], compute_dtype=model["torch_dtype"])


def _set(tree: dict, path: tuple, value) -> None:
    """``tree[path]`` = ``value``, refused where its shape or dtype is not
    the program's."""
    *parents, name = path
    for p in parents:
        tree = tree[p]
    old = tree[name]
    if old.shape != value.shape or old.dtype != value.dtype:
        raise ValueError(f"{'.'.join(map(str, path))}: program {old.shape} {old.dtype}, "
                         f"reference {value.shape} {value.dtype}")
    tree[name] = value


def seed_leaves(params: dict, model: dict, seed: int) -> None:
    """The reference's seeded norm scales, A_log, dt_bias, D, conv biases
    and gated-norm scales, in place of the initializer's constants."""
    import jax.numpy as jnp
    from repro.models.lm import layer_plan
    x = reference.extra_leaves(model, seed)
    kinds = reference.kinds_of(model)
    mamba_index = {li: i for i, li in enumerate(
        li for li, k in enumerate(kinds) if k == "mamba")}
    start = 0
    for s, (period, count) in enumerate(layer_plan(program_config(model))):
        for i, kind in enumerate(period):
            layers = [start + j * len(period) + i for j in range(count)]
            block = ("stacks", s, f"b{i}")
            _set(params, block + ("ln1", "scale"), x["ln1"][jnp.array(layers)])
            _set(params, block + ("ln2", "scale"), x["ln2"][jnp.array(layers)])
            if kind == "mamba":
                rows = jnp.array([mamba_index[li] for li in layers])
                for name in MAMBA_LEAVES:
                    _set(params, block + ("ssd", name), x[name][rows])
        start += len(period) * count
    _set(params, ("final_norm", "scale"), x["final"])


def shape(model: dict) -> reference.Shape:
    return reference.Shape.of(model)


served_gaps = reference.served_gaps
