"""One module per architecture, found by the configuration file's Hugging
Face ``model_type``: ``chipbench/archs/<model_type>.py``.

Each module gives the harness the four things that depend on the
architecture, and the harness looks up nothing else:

- ``program_config(model)``: the program's config (``repro.configs``) for
  a configuration file, every published size set from the file, refusing a
  file of another family;
- ``seed_leaves(params, model, seed)``: writes into the served weights the
  leaves that the program's initializer leaves at 0 and 1, drawn from the
  seed by the architecture's reference, with their shapes and dtypes
  checked;
- ``shape(model)``: an object with ``prefill_flops(prompt)``,
  ``decode_flops(pos)``, ``weight_bytes()`` and ``decode_slot_bytes(pos)``,
  the counts the metric readers divide by time;
- ``served_gaps(model, seed, prompts, outs, out_max, control=False)``: the
  reference teacher-forced over each prompt and its served tokens; returns
  ``gap`` (the reference's best logit minus the served token's, one per
  served token) and, with ``control``, ``control_gap``.

A configuration of a new family adds its module and the reference module it
calls; no file here changes.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

ARCHS = Path(__file__).resolve().parent


def for_model(model: dict):
    """The architecture module of a configuration file."""
    kind = model["model_type"]
    path = ARCHS / f"{kind}.py"
    if not path.is_file():
        raise KeyError(f"no architecture module for model_type {kind!r}: "
                       f"add {path}")
    return _load(path)


@functools.cache
def _load(path: Path):
    """One module object per file for the whole process."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_arch_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
