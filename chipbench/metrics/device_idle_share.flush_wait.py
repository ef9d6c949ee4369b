"""Share of the traced window in which the busiest chip is idle while the
broker waits out its flush deadline (``broker.flush_wait`` annotations), in
%, with the device's clock moved onto the host's (``annotations``)."""

from chipbench import annotations


def read(run):
    return annotations.device_idle_under(run, "broker.flush_wait")
