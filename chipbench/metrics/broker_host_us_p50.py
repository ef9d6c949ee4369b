"""Median host work of the broker's dispatch groups in the window, in us:
each ``broker.dispatch_group`` span's duration less the
``engine.device_wait`` spans under it (what is left is the stack, the
launch, the slices and the ticket fulfilment)."""

import numpy as np


def read(run):
    spans = run.spans or ()
    if not any(s.name == "engine.device_wait" for s in spans):
        return None  # the program does not split its device wait out
    children = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append(s)

    def waited(span_id):
        return sum(
            c.dur_us if c.name == "engine.device_wait" else waited(c.span_id)
            for c in children.get(span_id, ())
        )

    host = [s.dur_us - waited(s.span_id) for s in spans
            if s.name == "broker.dispatch_group"]
    return float(np.percentile(host, 50)) if host else None
