#!/usr/bin/env python3
"""The engine's host spans in a profiler trace, and the idle gaps between
the first device's operations named by them.

``ServeEngine`` opens a short ``serve.*`` span (``repro.serve.engine``:
admit, prefill, dispatch, read, emit) around each piece of host work in
its loop, on the device trace's clock.  The reduction, and the naming of
each gap, are ``xplane.py``'s; this adds every gap summed by its name.

    python chipbench/engine_spans.py <trace.xplane.pb[.gz]>

prints, as one JSON object, the reduction with the ten longest gaps so
named and every gap's count and seconds summed by its name.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import xplane

PREFIX = xplane.ENGINE_PREFIX
label_gaps = xplane.label_gaps


def load(path: Path) -> list:
    """(start_ns, end_ns, name, args) of every ``serve.*`` host span, by
    start."""
    return xplane.read(path)[2]


def by_label(gaps: list) -> dict:
    """Gaps and idle seconds summed by label."""
    out: dict = {}
    for secs, _, label in gaps:
        n, tot = out.get(label, (0, 0.0))
        out[label] = (n + 1, tot + secs)
    return {k: {"gaps": n, "idle_s": s} for k, (n, s) in
            sorted(out.items(), key=lambda kv: -kv[1][1])}


def reduce(path: Path):
    """``xplane.reduce``, and every gap summed by its name."""
    devices, harness, engine = xplane.read(path)
    return (xplane.reduce_devices(devices, harness, engine),
            by_label(label_gaps(devices[0], harness, engine)))


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
