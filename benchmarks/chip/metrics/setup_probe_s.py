"""Seconds of Plan probes (``kermit.probe`` spans) before the window
starts: the loop's searches in set-up."""


def read(run):
    sp = getattr(run, "spans", None)
    if sp is None:
        return None
    return sum(s.seconds for s in sp.named("kermit.probe", hi=0.0))
