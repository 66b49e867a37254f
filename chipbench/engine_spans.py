#!/usr/bin/env python3
"""The engine's host spans in a profiler trace, and the idle gaps between
the first device's operations named by them.

``ServeEngine`` opens a short ``serve.*`` span (``repro.serve.engine``:
admit, prefill, dispatch, read, emit) around each piece of host work in
its loop, on the device trace's clock.  A gap is named by the harness's
``submit`` or ``wait`` span open at its middle, as in ``xplane.py``; else
by the engine span open there; else ``engine_run``.  Busy time, the idle
share and the operations are ``xplane.py``'s, unchanged.

    python chipbench/engine_spans.py <trace.xplane.pb[.gz]>

prints, as one JSON object, the reduction with the ten longest gaps so
named and every gap's count and seconds summed by its name.
"""
from __future__ import annotations

import argparse
import dataclasses
import gzip
import json
import sys
from pathlib import Path

import numpy as np

import xplane

PREFIX = "serve."


def load(path: Path) -> list:
    """(start_ns, end_ns, name, args) of every ``serve.*`` host span, by
    start."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        pd = ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                           dict(e.stats))
                          for e in line.events if e.name.startswith(PREFIX)]
    return sorted(spans, key=lambda s: s[0])


def _gaps(dev: xplane.Device):
    """Start and end (ns) of every gap between the device's operations."""
    ops = np.asarray(dev.intervals(), dtype=float).reshape(-1, 2)
    lo, hi = ops[:-1, 1], ops[1:, 0]
    keep = hi > lo
    return lo[keep], hi[keep]


def _name_open(spans: list, t: np.ndarray, labels: np.ndarray) -> None:
    """Set ``labels[i]`` to the name of the span open at ``t[i]``, where
    one is; the spans are one thread's, which do not overlap."""
    if not spans:
        return
    starts = np.array([s[0] for s in spans], dtype=float)
    ends = np.array([s[1] for s in spans], dtype=float)
    names = np.array([s[2] for s in spans], dtype=object)
    i = np.searchsorted(starts, t, side="right") - 1
    hit = (i >= 0) & (t < ends[np.maximum(i, 0)])
    labels[hit] = names[i[hit]]


def label_gaps(dev: xplane.Device, harness: list, engine: list) -> list:
    """Every gap of ``dev`` as (seconds, middle ns, label), longest first
    (ties: the later first, as ``xplane.py`` orders them)."""
    lo, hi = _gaps(dev)
    mid = (lo + hi) / 2
    labels = np.full(len(mid), xplane.SERVING, dtype=object)
    # lowest precedence first: each later one overwrites
    _name_open([(s, e, n) for s, e, n, _ in engine], mid, labels)
    for name in reversed(xplane.LABEL_ORDER):
        _name_open([sp for sp in harness if sp[2] == name], mid, labels)
    order = np.lexsort((-mid, -(hi - lo)))
    return [((hi[i] - lo[i]) * 1e-9, mid[i], labels[i]) for i in order]


def by_label(gaps: list) -> dict:
    """Gaps and idle seconds summed by label."""
    out: dict = {}
    for secs, _, label in gaps:
        n, tot = out.get(label, (0, 0.0))
        out[label] = (n + 1, tot + secs)
    return {k: {"gaps": n, "idle_s": s} for k, (n, s) in
            sorted(out.items(), key=lambda kv: -kv[1][1])}


def reduce(path: Path):
    """``xplane.reduce`` with the idle gaps named by the engine's spans as
    well; and every gap summed by its name."""
    devices, harness = xplane.load(path)
    gaps = label_gaps(devices[0], harness, load(path))
    r = dataclasses.replace(
        xplane.reduce_devices(devices, harness),
        idle_gaps=[[label, secs] for secs, _, label in gaps[:xplane.TOP]])
    return r, by_label(gaps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", type=Path, help=".xplane.pb or .xplane.pb.gz")
    r, sums = reduce(ap.parse_args(argv).trace)
    print(json.dumps({"busy_s": r.busy_s, "window_s": r.window_s,
                      "idle_share_serving": r.idle_share_serving,
                      "decode_steps": r.decode_steps,
                      "idle_gaps": r.idle_gaps, "idle_by_label": sums}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
