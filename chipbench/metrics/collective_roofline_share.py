"""Least time of the window's collectives over the device time they took,
in %. Least time: every rank's input read once and every output written
once from HBM (``peaks.collective_least_bytes``) at the chip's HBM peak."""

from chipbench import peaks


def read(run):
    dev = run.reduction.busiest() if run.reduction else None
    if dev is None or dev.busy_us <= 0:
        return None
    least_s = sum(
        n * peaks.collective_least_bytes(int(d.comm_size), int(d.count) * 4)
        for d, n in run.window.ops
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (dev.busy_us / 1e6) if least_s > 0 else None
