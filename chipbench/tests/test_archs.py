"""The architecture modules: found by ``model_type``, the qwen2 module's
counts are ``work.Shape``'s, and a configuration whose module lives
outside ``chipbench/archs/`` is served and checked by new files alone."""
import json
import time

import pytest

import archs
import harness
import work
from conftest import BENCH, tiny_cell

SEED = 2**31 + 17
FUNCTIONS = ("program_config", "seed_leaves", "shape", "served_gaps")
QWEN2_7B = {"hidden_size": 3584, "num_attention_heads": 28,
            "num_key_value_heads": 4, "num_hidden_layers": 28,
            "intermediate_size": 18944, "vocab_size": 152064,
            "tie_word_embeddings": False, "torch_dtype": "bfloat16",
            "model_type": "qwen2"}


def published(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_qwen2_is_found_and_an_unknown_model_type_names_its_file():
    mod = archs.for_model(published("qwen2_0_5b"))
    assert all(callable(getattr(mod, f)) for f in FUNCTIONS)
    assert archs.for_model(published("qwen2_0_5b")) is mod
    with pytest.raises(KeyError, match="chipbench/archs/no_such_arch.py"):
        archs.for_model({"model_type": "no_such_arch"})


@pytest.mark.parametrize("model", [published("qwen2_0_5b"), QWEN2_7B],
                         ids=["qwen2_0_5b", "qwen2_7b"])
def test_qwen2_counts_are_work_shapes(model):
    mine = archs.for_model(model).shape(model)
    theirs = work.Shape.of(model)
    assert mine.weight_bytes() == theirs.weight_bytes()
    for pos in (0, 1, 255, 1023, 1024, 1535, 32767):
        assert mine.prefill_flops(pos + 1) == theirs.prefill_flops(pos + 1)
        assert mine.decode_flops(pos) == theirs.decode_flops(pos)
        assert mine.decode_slot_bytes(pos) == theirs.decode_slot_bytes(pos)


COPY = '''"""qwen2 under another model_type, outside chipbench/archs/."""
from archs.qwen2 import program_config, seed_leaves, shape, served_gaps
'''


def test_a_family_in_new_files_alone_is_served_correct(tmp_path, monkeypatch,
                                                       cpu_devices):
    (tmp_path / "qwen2_copy.py").write_text(COPY)
    monkeypatch.setattr(archs, "ARCHS", tmp_path)
    with pytest.raises(KeyError, match="qwen2.py"):
        archs.for_model({"model_type": "qwen2"})
    cell = tiny_cell(model_type="qwen2_copy")
    res = harness.run_cell(cell, SEED, 1.0, cpu_devices[:1],
                           time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["info"]["tokens_checked"] > 20
