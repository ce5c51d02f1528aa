"""Host-clock time of ``KermitSession.step_batch`` per telemetry window,
less the Plan probes it ran (s): the Monitor, Analyse and Plan work of the
manager itself."""


def read(run):
    windows = sum(s.windows for s in run.steps)
    if not windows:
        return None
    return sum(s.wall_s - s.probe_s for s in run.steps) / windows
