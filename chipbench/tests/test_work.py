"""FLOP and byte counts against numbers worked by hand, and the peaks."""
import json

import pytest

import work
from conftest import BENCH


def shape(name):
    return work.Shape.of(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


def test_qwen2_0_5b_counts():
    s = shape("qwen2_0_5b")
    # q 896x896, k/v 896x128 each, o 896x896, gate/up/down 896x4864 x3
    assert s.layer_matmul_params == 14_909_440
    # 2 x 24 x 14,909,440 x 256 + 4 x 24 x 14 x 64 x (256 x 257 / 2)
    # + 2 x 896 x 151,936
    assert s.prefill_flops(256) == 186_309_050_368
    # 2 x 24 x 14,909,440 + 4 x 24 x 14 x 64 x 301 + 2 x 896 x 151,936
    assert s.decode_flops(300) == 1_013_813_248
    # (24 x (14,909,440 + 1,152 biases + 1,792 norms) + 896 + 151,936 x 896)
    # x 2 bytes; the tied embedding is the LM head
    assert s.weight_bytes() == 988_065_536
    # K and V: 2 x 24 layers x 2 heads x 64 x 2 bytes
    assert s.kv_bytes_per_token == 12_288
    # embedding row + 301 positions read + 1 written
    assert s.decode_slot_bytes(300) == 1_792 + 302 * 12_288


QWEN2_7B = {"hidden_size": 3584, "num_attention_heads": 28,
            "num_key_value_heads": 4, "num_hidden_layers": 28,
            "intermediate_size": 18944, "vocab_size": 152064,
            "tie_word_embeddings": False, "torch_dtype": "bfloat16"}


def test_qwen2_7b_counts():
    s = work.Shape.of(QWEN2_7B)
    assert s.layer_matmul_params == 233_046_016
    assert s.prefill_flops(512) == 6_735_701_475_328
    # untied: the LM head (152,064 x 3,584) is read, the embedding table
    # only by rows
    assert s.weight_bytes() == 2 * (28 * 233_057_792 + 3_584
                                    + 152_064 * 3_584) == 14_141_238_272
    assert s.kv_bytes_per_token == 2 * 28 * 4 * 128 * 2


def test_peaks_known_and_unknown_kind():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
