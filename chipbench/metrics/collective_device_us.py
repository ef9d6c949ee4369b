"""Device-busy time of the busiest chip in the traced window over the
collectives the window completed, in us: the paper's on-NIC timer."""


def read(run):
    dev = run.reduction.busiest() if run.reduction else None
    n = run.window.attempted - run.window.failed
    if dev is None or n <= 0 or dev.busy_us <= 0:
        return None
    return dev.busy_us / n
