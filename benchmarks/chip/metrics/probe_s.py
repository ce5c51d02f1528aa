"""Seconds the Execute boundary spent replaying Plan probes inside the
window: the change in ``ServeExecutor.measure_seconds``.  In a window
where the loop only monitors, this reads 0."""


def read(run):
    return run.probe_s
