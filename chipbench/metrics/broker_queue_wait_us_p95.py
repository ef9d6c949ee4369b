"""95th percentile of the broker's queue wait (``broker.queue_wait`` spans:
from a request's enqueue to its group's dispatch), over the window, in us."""

import numpy as np


def read(run):
    waits = [s.dur_us for s in run.spans or () if s.name == "broker.queue_wait"]
    return float(np.percentile(waits, 95)) if waits else None
