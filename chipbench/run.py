#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic and its metrics are read by name
from ``BENCHMARK.json`` at the root of the checkout.  With ``--trace 0``
the result line carries the cell's end-to-end metrics; with ``--trace 1``
the last seconds of the window are profiled and it carries the per-layer
metrics, the device's busy seconds and a breakdown; the profile is left in
``.chipbench_trace/`` at the checkout's root, replaced by every traced run.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` when traced) and, last,
``checks``: each number compared with its limit, which also end stderr.
The run exits non-zero and prints no result where JAX finds no TPU or
fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".chipbench_trace"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def report(result: dict) -> None:
    checks = result.pop("checks")
    info = result.pop("info")
    print("info " + json.dumps(info), flush=True)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness
    cell = harness.Cell.load(spec, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              devices[:cell.chips], T_START,
                              TRACE_DIR if args.trace else None)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
