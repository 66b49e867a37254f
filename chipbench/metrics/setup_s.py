"""setup_s: host seconds from the process's start to the window's start:
imports, weights made on the device, compiles (or compile-cache loads),
the warm-up batch."""


def read(run):
    return run.setup_s
