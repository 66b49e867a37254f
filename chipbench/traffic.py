"""The one traffic generator: reads a traffic file, draws from the seed.

A traffic file (``chipbench/traffic/<name>.json``) holds parameters only:

- ``loop``: ``"open"``, Poisson arrivals at ``rate_per_s`` (the only kind
  the harness drives);
- ``prompt_tokens``: every prompt's length (the engine's prefill has no
  padding mask, so one cell has one prompt length);
- ``output``: a lognormal of output lengths (``median``, ``sigma``) clipped
  to ``[min, max]``;
- ``batch_size`` and ``max_seq``: the engine's slots and cache depth;
- ``check_requests``: how many served requests the correctness check takes.

The amount of work does not depend on the seed.  Output lengths are the
lognormal's quantiles taken along a Kronecker sequence (stride the golden
ratio) from a seeded start, so any run of consecutive requests holds short
and long ones in their published proportion.  Open-loop gaps are the
exponential's quantiles at ``(i + 0.5) / n`` in a seeded order.  The seed
picks the order, the start and the prompt tokens, drawn uniformly from the
vocabulary.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


@dataclasses.dataclass(frozen=True)
class Draw:
    due_s: float            # offset from the window's start
    prompt: np.ndarray      # (prompt_tokens,) int32
    n_out: int              # output tokens asked for


def load(path: Path) -> dict:
    spec = json.loads(Path(path).read_text())
    if spec["loop"] != "open":
        raise ValueError(f"{path}: loop must be open")
    o = spec["output"]
    if spec["prompt_tokens"] + o["max"] - 1 > spec["max_seq"]:
        raise ValueError(f"{path}: prompt_tokens + output max - 1 exceeds "
                         f"max_seq {spec['max_seq']}")
    return spec


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def output_lengths(spec: dict, n: int, rng: np.random.Generator
                   ) -> np.ndarray:
    o = spec["output"]
    u = (rng.random() + GOLDEN * np.arange(n)) % 1.0
    u = np.clip(u, 1e-9, 1 - 1e-9)
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    lens = np.rint(o["median"] * np.exp(o["sigma"] * z))
    return np.clip(lens, o["min"], o["max"]).astype(int)


def arrival_offsets(rate: float, seconds: float, rng: np.random.Generator
                    ) -> np.ndarray:
    """Offsets in ``[0, seconds)`` of a Poisson stream at ``rate``: the
    exponential's stratified quantiles in a seeded order, first at 0."""
    n = max(1, math.ceil(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])
    return due[due < seconds]


def draws(spec: dict, vocab: int, seed: int, seconds: float) -> list[Draw]:
    """Every request due in the window."""
    due = arrival_offsets(spec["rate_per_s"], seconds, rng_for(seed, 1))
    n = len(due)
    lens = output_lengths(spec, n, rng_for(seed, 2))
    prompts = rng_for(seed, 3).integers(
        0, vocab, size=(n, spec["prompt_tokens"]), dtype=np.int32)
    return [Draw(float(due[i]), prompts[i], int(lens[i])) for i in range(n)]


def warmup_prompts(spec: dict, vocab: int, seed: int) -> np.ndarray:
    """One batch of the cell's prompt shape, for the warm-up outside the
    window."""
    return rng_for(seed, 4).integers(
        0, vocab, size=(spec["batch_size"], spec["prompt_tokens"]),
        dtype=np.int32)
