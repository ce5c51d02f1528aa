# One benchmark per paper table/figure/claim. Prints ``name,value,derived``
# CSV rows (see DESIGN.md §7 for the figure -> benchmark index) and writes a
# machine-readable BENCH_analysis.json so the perf trajectory is tracked
# across PRs.
import argparse
import inspect
import json
import sys
import time
import traceback


def _jsonable(obj):
    """Best-effort conversion of benchmark return values (numpy scalars,
    dicts, tuples) into plain JSON types."""
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "item"):            # numpy scalar
        return obj.item()
    if isinstance(obj, (int, float, str, bool)) or obj is None:
        return obj
    return str(obj)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="",
                    help="comma-separated suite-name substrings to run")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced problem sizes (CI)")
    ap.add_argument("--json", default="BENCH_analysis.json",
                    help="machine-readable results path ('' to disable)")
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_analysis_latency, bench_autonomic_e2e,
                            bench_change_detector, bench_classifiers,
                            bench_clustering, bench_costmodel,
                            bench_explorer, bench_fleet, bench_kernels,
                            bench_knowledge, bench_monitor_throughput,
                            bench_predictor, bench_roofline,
                            bench_scenarios, bench_serve, bench_transition,
                            bench_zsl)
    suites = [
        ("change_detector[fig9]", bench_change_detector),
        ("classifiers[fig6]", bench_classifiers),
        ("clustering[fig10]", bench_clustering),
        ("transition[fig7]", bench_transition),
        ("predictor[claim96]", bench_predictor),
        ("zsl[claim83]", bench_zsl),
        ("kernels", bench_kernels),
        ("roofline[deliverable-g]", bench_roofline),
        ("plan_explorer[claims 30%/92.5% + batched search]", bench_explorer),
        ("costmodel[model-based plan gate]", bench_costmodel),
        ("knowledge[zsl k-way + drift + match throughput]", bench_knowledge),
        ("analysis_latency[perf]", bench_analysis_latency),
        ("monitor_throughput[perf]", bench_monitor_throughput),
        ("fleet[vmapped monitor + cross-tenant transfer]", bench_fleet),
        ("autonomic_e2e", bench_autonomic_e2e),
        ("scenarios[self-healing]", bench_scenarios),
        ("serving[autonomic serving gate]", bench_serve),
    ]
    only = [s.strip() for s in args.only.split(",") if s.strip()]
    if only:
        suites = [(n, m) for n, m in suites
                  if any(o in n for o in only)]
        if not suites:
            print(f"no suites match --only={args.only!r}", file=sys.stderr)
            sys.exit(2)

    failures = 0
    report = {}
    for name, mod in suites:
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        value, ok = None, True
        kw = {}
        if args.smoke and "smoke" in inspect.signature(mod.main).parameters:
            kw["smoke"] = True
        try:
            value = mod.main(**kw)
        except Exception:
            failures += 1
            ok = False
            print(f"{name},ERROR,", flush=True)
            traceback.print_exc()
        dt = time.time() - t0
        print(f"# {name} took {dt:.1f}s", flush=True)
        report[name] = {"ok": ok, "seconds": round(dt, 3),
                        "value": _jsonable(value)}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
