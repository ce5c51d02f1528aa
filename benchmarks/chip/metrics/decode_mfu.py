"""Model FLOP/s of the decode tokens the requests need, over the device
time of the decode program run for the client's traced calls, as a share of the
chip's bf16 peak (%).  A row counts only while its request still needs
tokens, and attention only over the positions that request can see."""


def read(run):
    if run.trace is None or not run.traced_calls:
        return None
    t = run.trace.program_in_serve_s.get("jit_serve_step", 0.0)
    if t <= 0:
        return None
    need = run.shapes.decode_steps(run.p, run.traced_calls,
                                   run.prompt_len, run.gen)
    return 100.0 * float(need[:, 0].sum()) / t / run.peak["bf16_flops_per_s"]
