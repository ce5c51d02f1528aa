"""Mean over the window's engine calls of the time inside
``ServeEngine.serve`` (``kermit.serve``) outside its ``kermit.prefill`` and
``kermit.decode`` spans (ms): the first token's argmax, the collect of the
tokens and their copy to the host, which ``ServeReport`` does not time."""


def read(run):
    sp = getattr(run, "spans", None)
    calls = sp.calls(0.0, sp.seconds) if sp is not None else []
    if not calls:
        return None
    return 1e3 * sum(
        c.seconds - sum(k.seconds for k in sp.children(c)
                        if k.name in ("kermit.prefill", "kermit.decode"))
        for c in calls) / len(calls)
