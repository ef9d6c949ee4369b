"""Median time the engine takes to launch a compiled schedule
(``engine.launch`` spans: the call into the jitted program, before its
device wait), over the window, in us."""

import numpy as np


def read(run):
    launches = [s.dur_us for s in run.spans or () if s.name == "engine.launch"]
    return float(np.percentile(launches, 50)) if launches else None
