"""decode_slot_use (%): the share of the decode steps' slots that emitted
a token, over the window and the drain: 100 x (tokens_out - admitted) /
(decode_steps x batch_size), from the engine's counters (``tokens_out``
counts each request's prefill token, one per request admitted).  None
where the engine does not count admitted requests."""


def read(run):
    st = run.stats
    if "admitted" not in st or not st.get("decode_steps"):
        return None
    slots = st["decode_steps"] * run.cell.traffic["batch_size"]
    return 100.0 * (st["tokens_out"] - st["admitted"]) / slots
