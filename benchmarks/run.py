"""Paper figures: runs each table/figure of ``paper_figures`` and prints
``name,us_per_call,derived`` CSV rows (the figure's own host wall time and
its claims), then per-figure data tables and paper-claim comparisons.
"""
from __future__ import annotations

import json
import time


def _time_us(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    return (time.perf_counter() - t0) / reps * 1e6, out


def run_paper_figures() -> None:
    from . import paper_figures as PF
    print("name,us_per_call,derived")
    for name, fn in PF.ALL_FIGS.items():
        us, (rows, claims) = _time_us(fn, reps=1)
        print(f"{name},{us:.0f},{json.dumps(claims)}")
    print()
    for name, fn in PF.ALL_FIGS.items():
        rows, claims = fn()
        print(f"== {name} ==")
        if rows:
            keys = sorted({k for r in rows for k in r})
            print(",".join(keys))
            for r in rows:
                print(",".join(str(r.get(k, "")) for k in keys))
        print(f"claims: {json.dumps(claims)}")
        print()


def main() -> None:
    run_paper_figures()


if __name__ == "__main__":
    main()
