"""Share of the time inside the engine's decode loops (``kermit.decode``
spans, from the first step's dispatch to the last token) in which no
program ran on the device (%), from the trace: the host setting the pace
step by step, apart from the boundaries of each call."""


def read(run):
    t = run.trace
    span = getattr(t, "program_span_s", {}).get("kermit.decode", 0.0)
    if not span or not t.program_s:
        return None
    return 100.0 * (1.0 - t.busy_in_program_span_s["kermit.decode"] / span)
