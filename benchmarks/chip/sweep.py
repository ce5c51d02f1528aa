"""Find a cell's knee: run its traffic at several rates on the chip.

    python3 benchmarks/chip/sweep.py --workload qwen2-1.5b.chat --seed 5 \\
        --seconds 51 --rates 0.9,1.2,1.5 --schedule-seeds 24301,77

One process, one engine; each rate, on each schedule, gets a fresh
session, its own warm-up and a window of ``--seconds``.  The first phase
of the cell's mix runs at each given rate (req/s); later phases keep
their shares of it.  The knee is the highest rate at which the backlog
does not grow over the window: the queue at the close stays within one
batch, and the window's requests drain within about one batch's service
time after it.  Prints one JSON
line per rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--schedule-seeds", default="",
                    help="schedules to sweep each rate on (default: the "
                         "cell's fixed one)")
    args = ap.parse_args(argv)
    import run as R
    R.device_stamp(1)
    import jax
    from chipbench import report
    from chipbench.cell import SCHEDULE_SEED, Cell
    from repro.runtime.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    enable_compile_cache()
    cell = Cell(args.workload)
    cell.build_engine(args.seed)
    share = float(cell.mix["phases"][0]["rate_knee_share"])
    schedules = [int(s) for s in args.schedule_seeds.split(",") if s] \
        or [SCHEDULE_SEED]
    for sched, rate in ((s, float(r)) for s in schedules
                        for r in args.rates.split(",")):
        run = cell.run(args.seed, args.seconds, knee=rate / share,
                       schedule_seed=sched)
        seg = run["seg"]
        e2e = report.end_to_end(run)
        print(json.dumps({
            "schedule_seed": sched, "rate_rps": rate,
            "requests": len(run["window"]),
            "backlog_at_close": run["facts"]["backlog_at_close"],
            "drain_s": float(np.max(seg.t_done) - args.seconds),
            "batches": [c.batch for c in seg.calls],
            "calls": run["facts"]["calls"]["rows"],
            "loop_warmup": run["facts"]["loop_warmup"],
            "loop_window": run["facts"]["loop_window"],
            "compile_in_window": run["facts"]["compile_in_window"],
            **e2e}), flush=True)
        run["session"].close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
