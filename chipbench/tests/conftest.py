"""CPU fixtures: a small Qwen2-style cell.

Run with ``python -m pytest chipbench/tests`` from the repository's root.
"""
import copy
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

# the published widths, so that logits, and the gaps compared with the
# configuration's limit, have the published scale; four layers and a cut
# vocabulary, so that a run fits a test
TINY_SIZES = {"num_hidden_layers": 4, "vocab_size": 4096,
              "max_window_layers": 4}


def tiny_model() -> dict:
    model = json.loads((BENCH / "configs" / "qwen2_0_5b.json").read_text())
    model.update(TINY_SIZES)
    return model


def tiny_traffic() -> dict:
    """Some hundreds of tokens checked, as a chip run checks thousands: a
    widest gap grows with the tokens it is taken over."""
    return {"loop": "open", "rate_per_s": 12.0, "prompt_tokens": 32,
            "output": {"dist": "lognormal", "median": 24, "sigma": 0.4,
                       "min": 8, "max": 48},
            "batch_size": 8, "max_seq": 80, "check_requests": 8}


def tiny_cell(**model_overrides):
    """The chat cell's metrics on a tiny model."""
    import harness
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    model = tiny_model()
    model.update(model_overrides)
    name = "qwen2_0_5b-chat-open"
    cell = harness.Cell.load(spec, name)
    return harness.Cell(name=name, chips=1, model=model,
                        traffic=tiny_traffic(),
                        end_to_end=copy.deepcopy(cell.end_to_end),
                        per_layer=copy.deepcopy(cell.per_layer))


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices()
