"""Share of the time inside ``ServeEngine.serve`` calls in which no program
ran on the device (%), from the trace: the host's per-token work, the
synchronisation at each call's end and the cache copy show here."""


def read(run):
    if run.trace is None or not run.trace.program_s:
        return None
    span = run.trace.span_s.get("serve", 0.0)
    if span <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_in_span_s["serve"] / span)
