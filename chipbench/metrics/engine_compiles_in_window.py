"""Schedules the engine compiled inside the window (``EngineTelemetry``
compiles); a warm cell reads 0."""


def read(run):
    return run.counter_delta("engine", "compiles")
