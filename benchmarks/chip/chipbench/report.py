"""End-to-end metrics of a run, and the view that per-layer readers get."""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

from chipbench import shapes

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peak(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"has {sorted(table)}")
    return table[device_kind]


def end_to_end(run: dict) -> dict:
    """The cell's end-to-end metrics over every request due in the window.

    A request that never completed counts as infinitely late, so a failure
    shows in the tail and in the rate."""
    seg, win = run["seg"], run["window"]
    out_tokens = float(np.sum(win.gen + 1))
    wall = float(np.max(seg.t_done))
    return {
        "ttft_p90_s": float(np.percentile(seg.ttft, 90)),
        "output_tokens_per_s": out_tokens / wall if np.isfinite(wall)
        else 0.0,
        "setup_s": float(run["setup_s"]),
    }


def span_check(run: dict, trace) -> dict:
    """Per host span, its seconds in the trace beside the host clock's
    seconds for the same calls (those that started inside the trace).  The
    trace's spans are what the device-trace metrics put device time
    against, so the two have to agree."""
    lo, hi = run["traced"]
    seg = run["seg"]
    host = {"serve": sum(c.t_end - c.t_dispatch for c in seg.calls
                         if lo <= c.t_dispatch < hi),
            "step_batch": sum(s.wall_s for s in seg.steps
                              if lo <= s.t_start < hi)}
    return {k: {"trace_s": trace.span_s.get(k, 0.0), "host_s": v}
            for k, v in host.items()}


@dataclass
class RunView:
    """What a per-layer reader sees of one run."""
    p: dict                  # the configuration's program block
    peak: dict               # this device's row of the peaks table
    calls: list              # engine calls dispatched inside the window
    traced_calls: list       # those of them inside the trace
    steps: list              # step_batch calls started inside the window
    prompt_len: np.ndarray   # per window request: its own prompt length
    gen: np.ndarray          # per window request: decode steps it needs
    tpot: np.ndarray         # per window request: its call's decode_s/steps
    probe_s: float           # executor measure seconds inside the window
    trace: object            # trace.Reduced, or None without --trace 1
    spans: object            # program_spans.Spans on the window's clock,
                             # or None where the program's spans were off
    shapes: object           # chipbench.shapes bound to the run's counts
                             # files (shapes.at)


def view(run: dict, p: dict, pk: dict, trace, spans, root: str) -> RunView:
    seconds = run["seconds"]
    seg, win = run["seg"], run["window"]
    lo, hi = run["traced"] if trace is not None else (0.0, 0.0)
    return RunView(p=p, peak=pk,
                   calls=[c for c in seg.calls if c.t_dispatch < seconds],
                   traced_calls=[c for c in seg.calls
                                 if lo <= c.t_dispatch < hi],
                   steps=[s for s in seg.steps if s.t_start < seconds],
                   prompt_len=win.prompt_len, gen=win.gen, tpot=seg.tpot,
                   probe_s=run["probe_s"], trace=trace, spans=spans,
                   shapes=shapes.at(root))
