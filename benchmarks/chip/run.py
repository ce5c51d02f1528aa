"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload qwen2-1.5b.chat --seed 7 \\
        --seconds 51 --trace 0

From the root of a checkout, on a machine whose JAX devices are TPUs.  The
cell, its configuration, its traffic mix and its per-layer metrics are
files under ``benchmarks/chip/`` found by name (``chipbench/registry.py``);
``BENCHMARK.json`` at the root says which metrics the cell reports.
With ``--trace 1`` the program's own spans (``repro.runtime.spans``) are
on from before set-up, and the per-layer readers get them
(``chipbench/program_spans.py``); with ``--trace 0`` they stay off.

Earlier stdout lines are facts about the run (device, set-up and window
compile seconds, the loop's decisions, the window's engine calls, the
bytes in use between them, idle share of the whole window, host spans in
the trace beside the host clock, the backlog at the close; traced, the
program's spans beside the engine's reports and the host clock, and where
each engine call's host time went).  The stamped
``memory_peak_bytes`` is the process's peak, reached in set-up.  The last
stderr lines give each number that decides ``correct`` beside its limit.
The last stdout line is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``; ``checks`` comes last.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def device_stamp(chips: int) -> dict:
    """The devices JAX sees; anything but enough TPUs ends the run."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"run.py: needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def fact(**kw):
    print(json.dumps(kw, default=float), flush=True)


def main(argv=None, *, root=None, bench=None, stamp=device_stamp,
         corrupt=None) -> int:
    """One run.  Tests pass ``root`` (a folder of cell files), ``bench``
    (the ``BENCHMARK.json`` object), ``stamp`` (in place of the look for a
    chip) and ``corrupt`` (a fault planted in the engine)."""
    t_start = time.perf_counter()
    args = parse(argv)
    if bench is None:
        with open(os.path.join(REPO, "BENCHMARK.json")) as f:
            bench = json.load(f)
    w = workload(bench, args.workload)
    device = stamp(int(w["chips"]))
    sys.path[:0] = [HERE, os.path.join(REPO, "src")]
    from repro.runtime import spans
    # a traced run records the program's own spans from before set-up; a
    # run without the trace records none
    log = spans.enable() if args.trace else None
    try:
        return measure(args, w, device, root, bench, corrupt, t_start, log)
    finally:
        if log is not None:
            spans.disable()


def measure(args, w, device, root, bench, corrupt, t_start, log) -> int:
    import jax
    from chipbench import check, program_spans as PS, registry, report
    from chipbench import trace as TR
    from chipbench.cell import Cell
    from repro.runtime.compile_cache import enable_compile_cache
    root = root or registry.ROOT

    # every program, however small, goes to the persistent cache, so that
    # a second run in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    fact(device=device, compile_cache_dir=enable_compile_cache())

    cell = Cell(args.workload, root)
    c0 = cell.clock.snapshot()
    t0 = time.perf_counter()
    cell.build_engine(args.seed)
    if corrupt is not None:
        corrupt(cell.engine)
    fact(engine_build={"wall_s": time.perf_counter() - t0,
                       **{k: v - c0[k] for k, v in
                          cell.clock.snapshot().items()}})
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    try:
        run = cell.run(args.seed, args.seconds, trace_dir, t_start=t_start)
        device["memory_peak_bytes"] = int(max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()[:int(w["chips"])]))
        reduced = program = None
        if trace_dir:
            t0 = time.perf_counter()
            events = TR.load_events(trace_dir)
            program = PS.reduce(events, PS.load(trace_dir))
            reduced = PS.into(TR.reduce(events), program)
            fact(trace_reduce_s=time.perf_counter() - t0)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for k, v in run["facts"].items():
        fact(**{k: v})

    e2e = report.end_to_end(run)
    metrics = {}
    if args.trace:
        book = log.to_json()
        sp = PS.Spans(book, PS.align(book, [c.t_dispatch for c in
                                            run["seg"].calls]),
                      run["seconds"])
        fact(program_spans={"recorded": len(book["spans"]),
                            "dropped": book["dropped"],
                            "check": PS.span_check(sp, run, program)})
        fact(call_phases=PS.call_phases(sp))
        view = report.view(run, cell.p, report.peak(device["kind"]), reduced,
                           sp, root)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        fact(window_idle_share=1.0 - reduced.busy_s / reduced.window_s,
             span_s=reduced.span_s, busy_in_span_s=reduced.busy_in_span_s,
             program_span_s=reduced.program_span_s,
             busy_in_program_span_s=reduced.busy_in_program_span_s,
             program_s=reduced.program_s)
        fact(span_check=report.span_check(run, reduced))
        for m in bench["per_layer"]:
            mod = registry.metric(m["name"], root)
            value = mod.read(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
            if hasattr(mod, "bounds") and value is not None:
                f_s, b_s, least = mod.bounds(view)
                fact(**{m["name"] + "_binds": {
                    "flop_bound_s": f_s, "byte_bound_s": b_s,
                    "binds": "bytes" if b_s >= f_s else "flops"}})
    else:
        for m in bench["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    fact(end_to_end=e2e)

    # -- is it correct: the program's state is freed, the reference runs --
    t0 = time.perf_counter()
    got = check.judge(cell, run, args.seed)
    fact(check={"wall_s": time.perf_counter() - t0,
                **{k: got[k] for k in ("sampled", "sampled_tokens")}})
    checks, correct = check.verdict(cell.config["check"], got)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    out = {"correct": bool(correct), "attempted": int(len(run["window"])),
           "failed": got["failed"], "metrics": metrics,
           "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced.device_ops,
                            "idle_gaps": reduced.idle_gaps}
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
