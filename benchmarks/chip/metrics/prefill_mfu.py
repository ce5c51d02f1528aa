"""Model FLOP/s of the requests' own prompt tokens, over the device time of
the prefill program run for the client's traced calls, as a share of the chip's
bf16 peak (%).  Padding rows and padded prompt positions count for
nothing, so padding shows as a lower share."""


def read(run):
    if run.trace is None or not run.traced_calls:
        return None
    t = run.trace.program_in_serve_s.get("jit_prefill_step", 0.0)
    if t <= 0:
        return None
    flops = sum(float(run.shapes.prefill_flops(
        run.p, run.prompt_len[c.requests]).sum()) for c in run.traced_calls)
    return 100.0 * flops / t / run.peak["bf16_flops_per_s"]
