"""Requests per engine dispatch the broker made in the window
(``ServiceTelemetry`` fused_requests over fused_dispatches)."""


def read(run):
    reqs = run.counter_delta("service", "fused_requests")
    disp = run.counter_delta("service", "fused_dispatches")
    return reqs / disp if reqs is not None and disp else None
