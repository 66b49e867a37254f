"""Roofline-term derivation from compiled XLA artifacts.

Three terms per (arch x shape x mesh), in seconds:

    compute    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``.  Collective
bytes are NOT in cost_analysis: we parse the post-SPMD optimized HLO
(``compiled.as_text()``) and sum the *output* buffer sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
(documented convention; operand vs result differs by <2x for these ops and
is applied uniformly across baselines and optimized versions).

Hardware constants: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# `%name = TYPE op-name(` — TYPE may be a tuple containing /*index=N*/
# comments (hence `.*?` rather than `[^=]*?`); the op name at call position
# is never %-prefixed (operand references are).
_OP_RE = re.compile(
    r"=\s*(?P<ty>\(?[a-z0-9]+\[.*?)\s*"
    r"(?<!%)(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()``, or ``{}`` where XLA reports none."""
    return compiled.cost_analysis() or {}


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every array shape in a (possibly tuple) HLO type."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind output bytes summed over the module (flat —
    correct only for fully-unrolled modules)."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        op = m.group("op")
        out[op] += _shape_bytes(m.group("ty"))
    return out


_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^\n]*\))?\s*"
                       r"(?:->[^\{]*)?\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?\)\s*,.*?condition=\%?([\w\.\-]+)"
                       r",\s*body=\%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> Dict[str, str]:
    comps: Dict[str, str] = {}
    cur_name = None
    cur_lines: List[str] = []
    for line in hlo_text.splitlines():
        m = _COMP_HDR.match(line)
        if m and not line.lstrip().startswith(("ROOT", "%constant")):
            if cur_name is not None:
                comps[cur_name] = "\n".join(cur_lines)
            cur_name = m.group(1)
            cur_lines = []
        elif cur_name is not None:
            cur_lines.append(line)
            if line.strip() == "}":
                comps[cur_name] = "\n".join(cur_lines)
                cur_name = None
                cur_lines = []
    if cur_name is not None:
        comps[cur_name] = "\n".join(cur_lines)
    return comps


def _trip_count(cond_text: str) -> int:
    """Loop bound heuristic: the largest integer literal in the while
    condition (scan conditions compare the induction var to the length)."""
    consts = [int(c) for c in _CONST_RE.findall(cond_text)]
    return max(consts) if consts else 1


_CALL_RE = re.compile(r"(?:call\(|fusion\().*?(?:to_apply|calls)=\%?"
                      r"([\w\.\-]+)")


def collective_bytes_while_aware(hlo_text: str,
                                 entry: Optional[str] = None
                                 ) -> Dict[str, int]:
    """Collective output bytes with while-loop bodies multiplied by their
    trip counts, and ``call``/fusion edges traversed with the caller's
    multiplier (at -O0 XLA does not inline calls, so e.g. shard_map bodies
    live in separate computations reached via call ops).
    """
    comps = _split_computations(hlo_text)
    entry_m = re.search(r"ENTRY\s+%?([\w\.\-]+)", hlo_text)
    entry = entry or (entry_m.group(1) if entry_m else None)
    if entry is None or entry not in comps:
        return collective_bytes(hlo_text)

    memo: Dict[str, Dict[str, int]] = {}

    def total(name: str, depth: int = 0) -> Dict[str, int]:
        """Bytes attributable to one execution of computation ``name``."""
        if name in memo:
            return memo[name]
        text = comps.get(name, "")
        out = {k: 0 for k in _COLLECTIVES}
        if depth > 16 or not text:
            return out
        memo[name] = out  # guard recursion
        for m in _OP_RE.finditer(text):
            out[m.group("op")] += _shape_bytes(m.group("ty"))
        for w in _WHILE_RE.finditer(text):
            cond, body = w.group(1), w.group(2)
            trips = _trip_count(comps.get(cond, ""))
            inner = total(body, depth + 1)
            for k in out:
                out[k] += trips * inner[k]
        for c in _CALL_RE.finditer(text):
            target = c.group(1)
            if target in comps and target != name:
                inner = total(target, depth + 1)
                for k in out:
                    out[k] += inner[k]
        memo[name] = out
        return out

    return total(entry)


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float            # whole-step FLOPs (all chips)
    hlo_bytes: float            # whole-step HBM bytes (all chips)
    coll_bytes: float           # per-chip collective bytes (see note)
    coll_breakdown: Dict[str, int]
    model_flops: float          # 6*N*D (or 6*N_active*D) convention

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Optimistic overlap model: step >= max(terms)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / max(self.hlo_flops, 1.0)

    @property
    def mfu(self) -> float:
        """Model FLOPs / (chips * peak * step_time) under the overlap model."""
        t = self.step_time
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * PEAK_FLOPS * t)

    def as_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_breakdown": self.coll_breakdown,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu": self.mfu,
        }


def model_flops_for(cfg, shape, kind: str) -> float:
    """6*N*D convention (N = active params for MoE).

    train: D = global tokens, x3 for fwd+bwd (6*N*D already includes bwd:
    2*N*D fwd + 4*N*D bwd = 6*N*D).  prefill: 2*N*D.  decode: 2*N*B.
    Attention window/quadratic terms are intentionally excluded (the
    convention) — the useful_flops_ratio column surfaces the gap.
    """
    n_active = cfg.active_param_count()
    if kind == "train":
        d_tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * d_tokens
    if kind == "prefill":
        d_tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * d_tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token each
