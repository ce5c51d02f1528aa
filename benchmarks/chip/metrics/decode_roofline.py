"""The least time the chip could take for the decode steps the requests
need, over the device time of the decode program run for the client's
traced calls, as a share (%).  Each step's least time is the larger of its
operations over peak FLOP/s and its bytes over peak bandwidth: every
weight once, plus the keys and values of each live row's visible
positions, or each live row's state read and written
(``chipbench.shapes``)."""
import numpy as np


def bounds(run):
    """(seconds bound by FLOPs, seconds bound by bytes, least seconds)."""
    need = run.shapes.decode_steps(run.p, run.traced_calls,
                                   run.prompt_len, run.gen)
    f = need[:, 0] / run.peak["bf16_flops_per_s"]
    b = need[:, 1] / run.peak["hbm_bytes_per_s"]
    return float(f.sum()), float(b.sum()), float(np.maximum(f, b).sum())


def read(run):
    if run.trace is None or not run.traced_calls:
        return None
    t = run.trace.program_in_serve_s.get("jit_serve_step", 0.0)
    if t <= 0:
        return None
    return 100.0 * bounds(run)[2] / t
