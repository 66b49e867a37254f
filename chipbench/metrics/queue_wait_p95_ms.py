"""queue_wait_p95_ms: 95th percentile over the window's requests of the
time from ``engine.submit`` to the forming of the request's batch
(``Request.admitted_at - Request.submitted_at``, both stamped by the engine
on the host clock).  None where the engine stamps neither."""
import numpy as np


def read(run):
    waits = [(s.req.admitted_at - s.req.submitted_at) * 1e3
             for s in run.served
             if getattr(s.req, "admitted_at", None) is not None
             and getattr(s.req, "submitted_at", None) is not None]
    return float(np.percentile(waits, 95)) if waits else None
