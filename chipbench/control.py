#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's widest logit gap and
the fp8 control's, over many seeds in one process.

    python chipbench/control.py --workload <cell> --seeds 1 2 3 \
        --seconds <s>

For each seed: weights from the seed, the cell's traffic at its own load
for a short window (long enough to finish the mix's longest requests),
then the check's sample teacher-forced through the float32 reference,
which reads the gap of every served token and the gap of the token the
control (the reference with fp8 projections) puts first.  Prints one JSON
line per seed and a summary: the lower reading (the program's largest
gap) and the upper one (the control's smallest).  The benchmark's own runs
do not run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def readings(cell, seeds, seconds, devices) -> dict:
    import harness
    rows = []
    for seed in seeds:
        run, _, _ = harness.serve_window(cell, seed, seconds, devices,
                                         time.perf_counter())
        faults = [f for s in run.served if (f := s.fault())]
        if faults:
            raise RuntimeError(f"seed {seed}: {faults[:3]}")
        row = {"seed": seed, **harness.check(cell, seed, run.served,
                                             control=True)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del run
        gc.collect()
    lower = max(r["max_logit_gap"] for r in rows)
    upper = min(r["control_max_logit_gap"] for r in rows)
    return {"lower": lower, "upper": upper, "ratio": upper / lower
            if lower > 0 else None, "seeds": len(rows)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness
    import jax
    cell = harness.Cell.load(spec, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("chipbench control: no TPU", file=sys.stderr)
        return 2
    print(json.dumps({"summary": readings(cell, args.seeds, args.seconds,
                                          devices[:cell.chips])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
