"""ttft_p95_ms: 95th percentile over the window's requests of the time from
a request's due time to its first token's stamp on the host."""
import numpy as np


def read(run):
    ttft = [(s.stamps[0] - s.due) * 1e3 for s in run.served if s.stamps]
    return float(np.percentile(ttft, 95)) if ttft else None
