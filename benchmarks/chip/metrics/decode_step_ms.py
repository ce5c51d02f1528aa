"""Engine time per decode step of the batch, from ``ServeReport.decode_s``
over its steps (ms)."""


def read(run):
    steps = sum(c.steps for c in run.calls)
    if not steps:
        return None
    return 1e3 * sum(c.decode_s for c in run.calls) / steps
