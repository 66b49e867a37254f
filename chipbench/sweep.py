#!/usr/bin/env python3
"""Find an open-loop cell's knee once, by a sweep of fixed rates.

    python chipbench/sweep.py --workload <cell> --rates 8 12 16 \
        --seeds 1 2 --seconds <s>

One process, one engine per seed: for each rate the cell's traffic runs for
``seconds`` and drains.  The queue grew through the window where the
requests due in its last quarter waited, on the mean, more than 1.5 times
as long for their first token as those due in its first quarter
(``grows``).  The knee is the highest rate at which it did not grow on any
seed; the cell's traffic file is then set to 4/5 of it by hand.  The
benchmark's own runs do not run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
GROWS = 1.5     # last-quarter over first-quarter mean TTFT


def one_rate(engine, cell, seed: int, rate: float, seconds: float) -> dict:
    import numpy as np
    import harness
    c = dataclasses.replace(cell, traffic={**cell.traffic,
                                           "rate_per_s": rate})
    before = dict(engine.stats)
    t0 = time.perf_counter() + harness.START_LEAD_S
    served = harness.drive(engine, c, seed, seconds, t0)
    ttft = np.array([s.stamps[0] - s.due for s in served])
    q = max(1, len(ttft) // 4)
    first, last_q = ttft[:q].mean(), ttft[-q:].mean()
    last = max(s.stamps[-1] for s in served)
    batches = engine.stats["prefills"] - before["prefills"]
    return {"seed": seed, "rate": rate, "requests": len(served),
            "ttft_p50_ms": float(np.median(ttft) * 1e3),
            "ttft_p95_ms": float(np.percentile(ttft, 95) * 1e3),
            "ttft_first_quarter_ms": float(first * 1e3),
            "ttft_last_quarter_ms": float(last_q * 1e3),
            "grows": bool(last_q > GROWS * first),
            "drain_s": last - (t0 + seconds),
            "batch_s": (last - t0) / max(batches, 1),
            "tokens_per_s": sum(len(s.stamps) for s in served)
            / (last - t0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cell = harness.Cell.load(spec, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or cell.traffic["loop"] != "open":
        print("chipbench sweep: needs a TPU and an open-loop cell",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    for seed in args.seeds:
        engine = harness.build_engine(cell, seed)
        harness.warm_up(engine, cell, seed)
        for rate in args.rates:
            print(json.dumps(one_rate(engine, cell, seed, rate,
                                      args.seconds)), flush=True)
        del engine
    print(json.dumps({"device": harness.device_info(devices[:cell.chips])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
