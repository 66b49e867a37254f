"""mfu.prefill (%): the FLOPs the window's prompts need over the engine's
prefill seconds (``stats["prefill_s"]``, each closed by a device sync)
times the chips' bf16 peak."""


def read(run):
    secs = run.stats["prefill_s"]
    if not run.served or secs <= 0:
        return None
    flops = sum(run.shape.prefill_flops(len(s.prompt)) for s in run.served)
    peak = run.peaks["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * flops / (secs * peak)
