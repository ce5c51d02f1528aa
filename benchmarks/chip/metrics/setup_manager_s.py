"""Seconds of ``KermitSession.step_batch`` (``kermit.step_batch`` spans)
before the window starts, less the Plan probes inside them: the Monitor,
Analyse and Plan work of the manager itself in set-up."""


def read(run):
    sp = getattr(run, "spans", None)
    if sp is None:
        return None
    return sum(s.seconds - sum(p.seconds for p in sp.within(s, "kermit.probe"))
               for s in sp.named("kermit.step_batch", hi=0.0))
