import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")   # a CPU-only tool: never take a chip

# Memory-budget post-pass for hillclimb results: walk the search trace in
# ascending estimated-time order, full-compile each candidate, and keep the
# fastest one whose per-device temp memory fits the HBM budget. Writes the
# result back into <arch>__<shape>__opt.json as "budgeted".
#
#   PYTHONPATH=src python -m repro.launch.verify_budget --arch qwen2-1.5b \
#       --shape train_4k [--budget-gb 16] [--max-tries 6]

import argparse
import json

from repro.configs.base import SHAPES, Tunables
from repro.configs.registry import ARCHS
from repro.launch.dryrun import OUT_ROOT, lower_cell


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--shape", choices=list(SHAPES), required=True)
    ap.add_argument("--budget-gb", type=float, default=16.0)
    ap.add_argument("--max-tries", type=int, default=6)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args(argv)

    mesh = "2x16x16" if args.multi_pod else "16x16"
    path = OUT_ROOT / mesh / f"{args.arch}__{args.shape}__opt.json"
    rec = json.loads(path.read_text())
    trace = [t for t in rec["hillclimb"]["trace"] if "est_s" in t]
    trace.sort(key=lambda t: t["est_s"])
    budget = args.budget_gb * 1e9

    # composite memory-saver candidates derived from the unconstrained best:
    # coordinate descent rarely revisits remat/microbatches after flipping
    # them early, but they are the main temp-memory levers.
    best_tun = dict(trace[0]["tun"])
    seen = {json.dumps(t["tun"], sort_keys=True) for t in trace}
    for extra in ({"remat": "dots"}, {"remat": "full"},
                  {"remat": "full", "microbatches": 8},
                  {"remat": "dots", "microbatches": 4},
                  {"zero3": True},
                  {"zero3": True, "remat": "dots"},
                  {"zero3": True, "remat": "full", "microbatches": 8}):
        cand = dict(best_tun, **extra)
        if json.dumps(cand, sort_keys=True) not in seen:
            trace.append({"tun": cand, "est_s": float("nan"),
                          "synthetic": True})

    candidates = trace[:args.max_tries] + \
        [t for t in trace if t.get("synthetic")]
    chosen = None
    for t in candidates:
        tun = Tunables(**t["tun"])
        print(f"[verify] candidate est={t['est_s']:.3f}s "
              f"{json.dumps(t['tun'])}", flush=True)
        full = lower_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                          tun=tun, verbose=False)
        if t.get("synthetic"):           # estimate came with the full compile
            r = full["roofline"]
            t["est_s"] = max(r["compute_s"], r["memory_s"],
                             r["collective_s"])
        temp = full["memory"].get("temp_size_in_bytes") or 0
        print(f"[verify]   est={t['est_s']:.3f}s temp={temp/1e9:.1f}GB "
              f"({'FITS' if temp <= budget else 'over budget'})", flush=True)
        t["temp_bytes"] = temp
        if temp <= budget:
            chosen = (t, full)
            break
    if chosen is None:
        print("[verify] no candidate fit the budget; keeping unconstrained")
        rec["hillclimb"]["budgeted"] = None
    else:
        t, full = chosen
        rec["hillclimb"]["budgeted"] = {
            "tun": t["tun"], "est_s": t["est_s"],
            "temp_bytes": t["temp_bytes"],
            "roofline": full["roofline"], "memory": full["memory"],
        }
        base = rec["hillclimb"]["baseline"]["est_s"]
        print(f"[verify] budgeted optimum: {base:.3f}s -> {t['est_s']:.3f}s "
              f"({base/max(t['est_s'],1e-9):.2f}x) within "
              f"{args.budget_gb:.0f}GB", flush=True)
    path.write_text(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
