"""Share of the traced window in which the busiest chip ran no operation,
in %, for the collective cells."""


def read(run):
    dev = run.reduction.busiest() if run.reduction else None
    if dev is None or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - dev.busy_us / 1e6 / run.trace_window_s)
