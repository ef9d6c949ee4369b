"""Share of the traced window in which the busiest chip is idle while the
broker's dispatch thread works on a group (``broker.dispatch_group``
annotations): the chip waits on host work. In %, with the device's clock
moved onto the host's (``annotations``)."""

from chipbench import annotations


def read(run):
    return annotations.device_idle_under(run, "broker.dispatch_group")
