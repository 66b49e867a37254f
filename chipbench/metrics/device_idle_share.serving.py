"""device_idle_share.serving (%): from the profiler trace, the share of
the traced time with requests in the engine (the harness's ``wait`` spans
left out) in which no operation ran on the device, averaged over the
cell's chips."""


def read(run):
    if run.trace is None or run.trace.idle_share_serving is None:
        return None
    return 100.0 * run.trace.idle_share_serving
