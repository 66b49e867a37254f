"""mfu.decode (%): the FLOPs the window's decoded tokens need over the
engine's decode seconds (``stats["decode_s"]``) times the chips' bf16
peak."""


def read(run):
    secs = run.stats["decode_s"]
    if secs <= 0:
        return None
    flops = sum(run.shape.decode_flops(len(s.prompt) + i - 1)
                for s in run.served for i in range(1, len(s.stamps)))
    peak = run.peaks["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * flops / (secs * peak)
