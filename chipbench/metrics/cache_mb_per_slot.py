"""cache_mb_per_slot (MB, 10^6 bytes): the decode cache's bytes per batch
slot, K/V and recurrent state together (``stats["cache_bytes"]``, set by
``engine.compile``).  A decode step reads each slot's share and rewrites
its state, so fewer bytes a slot means fewer bytes a step.  It is fixed by
the program and the cell (dtype, layout, ``max_seq``), not by the run.
None where the engine does not count them."""


def read(run):
    cache = run.stats.get("cache_bytes")
    if not isinstance(cache, dict):
        return None
    return sum(cache.values()) / run.cell.traffic["batch_size"] / 1e6
