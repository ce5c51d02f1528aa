"""Engine prefill time per thousand prompt positions computed (batch times
padded prompt), from ``ServeReport.prefill_s`` (ms)."""


def read(run):
    if not run.calls:
        return None
    positions = sum(c.batch * c.prompt_len for c in run.calls)
    return 1e6 * sum(c.prefill_s for c in run.calls) / positions
