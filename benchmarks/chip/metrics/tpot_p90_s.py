"""90th percentile over the window's requests of the mean gap between a
request's output tokens after the first: its engine call's
``ServeReport.decode_s`` over the call's steps (s).  Every request of a
static batch gets its call's gap, so the tail is that of the slowest
calls; one engine call slowed on the host moves it (``PERF.md``)."""
import numpy as np


def read(run):
    if not len(run.tpot):
        return None
    return float(np.percentile(run.tpot, 90))
