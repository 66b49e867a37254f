"""hbm_roofline.decode (%): the bytes the decode steps need over the
engine's decode seconds times the chips' HBM bandwidth.  A step needs every
weight once; each decoded token needs its slot's K/V up to its position
and the new K/V written."""


def read(run):
    secs, steps = run.stats["decode_s"], run.stats["decode_steps"]
    if secs <= 0 or not steps:
        return None
    shape = run.shape
    need = steps * shape.weight_bytes() + sum(
        shape.decode_slot_bytes(len(s.prompt) + i - 1)
        for s in run.served for i in range(1, len(s.stamps)))
    peak = run.peaks["hbm_bytes_per_s"] * run.cell.chips
    return 100.0 * need / (secs * peak)
