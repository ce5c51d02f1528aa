"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 benchmarks/chip/control.py --workload qwen2-1.5b.chat \\
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 51

One process holds the chip and builds the engine once.  For each seed it
draws that seed's weights and traffic, runs a short window of the cell's
traffic through the timed path as ``run.py`` does, but without the loop's
warm-up traffic before it, and reads every number that ``correct``
compares; for the control seeds it also reads the control, the reference
in the program's place at float8 (``chipbench.check``), and judges it
against the cell's limits as ``run.py`` judges a run
(``control_correct``, which has to come out false).  Prints one JSON line
per seed.  The largest program reading over a dozen seeds or more and
the smallest control reading bound each limit (``PERF.md``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run as R
    R.device_stamp(1)
    import jax
    from chipbench import check, report
    from chipbench.cell import Cell
    from repro.runtime.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    cell = Cell(args.workload)
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if i == 0:
            cell.build_engine(seed)
        else:
            cell.set_weights(seed)
        run = cell.run(seed, args.seconds, loop_warmup=False)
        got = check.judge(cell, run, seed, control=seed in controls)
        if seed in controls:
            # the control in the program's place, judged as a run is
            checks, ok = check.verdict(cell.config["check"], dict(
                got, logit_gap=got["control_logit_gap"]))
            got.update(control_correct=ok, control_checks=checks)
        print(json.dumps({"seed": seed, **got, **report.end_to_end(run),
                          "loop_window": run["facts"]["loop_window"],
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
