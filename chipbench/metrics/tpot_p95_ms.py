"""tpot_p95_ms: 95th percentile over the window's requests of two or more
tokens of (last stamp - first stamp) / (tokens - 1)."""
import numpy as np


def read(run):
    tpot = [(s.stamps[-1] - s.stamps[0]) / (len(s.stamps) - 1) * 1e3
            for s in run.served if len(s.stamps) >= 2]
    return float(np.percentile(tpot, 95)) if tpot else None
