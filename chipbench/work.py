"""Work a served token needs, from the configuration's shapes, and the
chip's peaks.

FLOPs are what the algorithm needs, not what today's code does: 2 per
matmul parameter per token, causal attention over the positions at or
before the query's own, and the LM head only where a logit is used (a
prompt's last position and every decoded token).  Bytes of a decode step
are every weight once, each live slot's K/V up to its position, and the new
K/V written.  A change that skips work the code does today (the full S x S
block grid, the whole ``max_seq`` slab) therefore cannot read above 100%.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    dtype_bytes: int

    @classmethod
    def of(cls, model: dict) -> "Shape":
        """From a configuration file (Hugging Face keys)."""
        d, h = model["hidden_size"], model["num_attention_heads"]
        return cls(layers=model["num_hidden_layers"], d=d, heads=h,
                   kv_heads=model["num_key_value_heads"],
                   head_dim=model.get("head_dim", d // h),
                   ff=model["intermediate_size"], vocab=model["vocab_size"],
                   tied=bool(model["tie_word_embeddings"]),
                   dtype_bytes=DTYPE_BYTES[model["torch_dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * q + 2 * self.d * kv + q * self.d + 3 * self.d * self.ff

    @property
    def layer_params(self) -> int:
        """Matmuls, q/k/v biases and the two norms."""
        return (self.layer_matmul_params
                + (self.heads + 2 * self.kv_heads) * self.head_dim
                + 2 * self.d)

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position over every layer."""
        return (2 * self.layers * self.kv_heads * self.head_dim
                * self.dtype_bytes)

    def attention_flops(self, keys: int) -> int:
        """One query over ``keys`` positions in every layer (QK and PV)."""
        return 4 * self.layers * self.heads * self.head_dim * keys

    @property
    def head_flops(self) -> int:
        return 2 * self.d * self.vocab

    def prefill_flops(self, prompt: int) -> int:
        """One prompt: every position through every layer, causal
        attention, the LM head at the last position only."""
        pairs = prompt * (prompt + 1) // 2
        return (2 * self.layers * self.layer_matmul_params * prompt
                + self.attention_flops(pairs) + self.head_flops)

    def decode_flops(self, pos: int) -> int:
        """One decoded token whose query sits at position ``pos``."""
        return (2 * self.layers * self.layer_matmul_params
                + self.attention_flops(pos + 1) + self.head_flops)

    def weight_bytes(self) -> int:
        """Every weight a decode step reads once: the layers, the final
        norm and the LM head (the embedding's rows are counted per slot)."""
        return (self.layers * self.layer_params + self.d
                + self.vocab * self.d) * self.dtype_bytes

    def decode_slot_bytes(self, pos: int) -> int:
        """One live slot of a decode step at position ``pos``: its
        embedding row, its K/V at positions 0..pos read, the new K/V
        written."""
        return (self.d * self.dtype_bytes
                + (pos + 2) * self.kv_bytes_per_token)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
