import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")   # a CPU-only tool: never take a chip

# §Perf hillclimb: KERMIT's Explorer searches the runtime-tunable space with
# the DRY-RUN ROOFLINE as the objective — exactly the paper's plug-in loop,
# with "measured job time" replaced by the compiled-artifact cost model:
#
#   est_step_time(tun) = max(compute_s, memory_s, collective_s)   [probes]
#
# The search trace is the hypothesis->change->before/after log EXPERIMENTS.md
# §Perf requires; the winning config is re-lowered with the FULL compile to
# verify per-device memory, and stored as <arch>__<shape>__opt.json. The
# found optimum is also written into a WorkloadDB, so the serving/training
# launcher can reuse it exactly like the paper's Algorithm 1 does.
#
#   PYTHONPATH=src python -m repro.launch.hillclimb --arch qwen2-1.5b \
#       --shape train_4k

import argparse
import json
import math
import time
from pathlib import Path

from repro.analysis.roofline import model_flops, roofline_terms, count_params
from repro.configs.base import DEFAULT_TUNABLES, SHAPES, Tunables
from repro.configs.registry import ARCHS, get_config, get_shape
from repro.core.explorer import DEFAULT_SPACE, Explorer
from repro.kermit.executor import ExecutorObjective, MeasureCounters
from repro.launch.dryrun import (OUT_ROOT, lower_cell, probe_cost, _lower,
                                 run_cell)
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.optim.adamw import OptConfig
from repro.sharding import rules

import numpy as np

import jax

HBM_BUDGET = 16e9     # v5e per-chip


def knob_space(cfg, kind: str) -> dict:
    """Shape/family-specific overrides layered over the one source of truth,
    ``core/explorer.DEFAULT_SPACE`` — candidate lists for shared knobs come
    from there, so the launcher's grid can't silently diverge from the
    on-line Plan phase's.  ``zero3``/``donate`` are launcher-only knobs."""
    if kind in ("decode",):
        space = {"zero3": [True, False], "donate": [True]}
        if cfg.moe is not None:
            # decode sweeps the capacity extremes, not the fine steps
            space["capacity_factor"] = [
                v for v in DEFAULT_SPACE["capacity_factor"] if v != 1.5]
        return space
    space = {
        "remat": list(DEFAULT_SPACE["remat"]),
        "microbatches": list(DEFAULT_SPACE["microbatches"]),
        "seq_parallel": list(DEFAULT_SPACE["seq_parallel"]),
        "zero3": [True, False],
    }
    if cfg.attn_free or cfg.family == "hybrid":
        space["ssm_chunk"] = list(DEFAULT_SPACE["ssm_chunk"])
    else:
        space["attn_q_chunk"] = list(DEFAULT_SPACE["attn_q_chunk"])
    if cfg.moe is not None:
        # training keeps the sub-2.0 capacity steps (2.0 OOMs the probes)
        space["capacity_factor"] = [
            v for v in DEFAULT_SPACE["capacity_factor"] if v <= 1.5]
    if kind == "prefill":
        space.pop("microbatches")
        space.pop("remat")
    return space


class RooflineExecutor(MeasureCounters):
    """Execute boundary for the dry-run hillclimb (the Plan phase's
    ``BatchExecutor`` protocol over compiled-artifact probes).

    ``measure`` probes one candidate; ``measure_batch`` probes each
    candidate's raw cost terms (HLO lowering itself cannot be batched) and
    then reduces ``est = max(compute, memory, collective)`` across the whole
    batch in one vectorized pass over the stacked term matrix — the Explorer
    sweeps a knob per dispatch.  Trace rows and progress prints land in
    evaluation order as each probe completes.  Counter surface is the shared
    ``MeasureCounters`` shape.
    """

    def __init__(self, cfg, shape, oc, mesh, chips, mf, trace):
        self.cfg, self.shape, self.oc, self.mesh = cfg, shape, oc, mesh
        self.chips, self.mf, self.trace = chips, mf, trace
        self.current = DEFAULT_TUNABLES
        self._init_counters()

    def apply(self, tun: Tunables) -> None:
        self._count_apply(tun)

    def _probe_one(self, tun: Tunables):
        """Probe one candidate, append its trace row (error or est) in
        order, and return its term triple (+inf on failure so the commit
        scan skips it)."""
        t0 = time.time()
        try:
            cost, coll = probe_cost(self.cfg, self.shape, tun, self.oc,
                                    self.mesh)
        except Exception as e:
            self.trace.append({"tun": tun.as_dict(), "error": repr(e)})
            return (math.inf,) * 3
        rl = roofline_terms(cost, coll, chips=self.chips,
                            model_flops=self.mf)
        est = max(rl.compute_s, rl.memory_s, rl.collective_s)
        self.trace.append({"tun": tun.as_dict(), "est_s": est,
                           "compute_s": rl.compute_s,
                           "memory_s": rl.memory_s,
                           "collective_s": rl.collective_s,
                           "bottleneck": rl.bottleneck,
                           "eval_wall_s": round(time.time() - t0, 1)})
        print(f"  eval est={est:.3f}s bn={rl.bottleneck} "
              f"({json.dumps(tun.as_dict())})", flush=True)
        return (rl.compute_s, rl.memory_s, rl.collective_s)

    def measure(self) -> float:
        with self._measuring():
            return float(max(self._probe_one(self.current)))

    def measure_batch(self, candidates) -> list:
        candidates = list(candidates)
        with self._measuring(len(candidates), batch=True):
            # vectorized roofline reduction over the whole knob sweep
            terms = np.array([self._probe_one(c) for c in candidates],
                             np.float64).reshape(-1, 3)
        return [float(e) for e in terms.max(axis=1)]


def hillclimb(arch: str, shape_name: str, *, multi_pod=False):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules.set_mesh(mesh)
    chips = mesh.devices.size
    oc = OptConfig()

    if shape.kind == "train":
        sds = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
        _, n_active = count_params(sds, cfg)
    else:
        sds = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
        _, n_active = count_params(sds, cfg)
    mf = model_flops(cfg, shape, n_active)

    trace = []
    rex = RooflineExecutor(cfg, shape, oc, mesh, chips, mf, trace)
    objective = ExecutorObjective(rex)      # batched roofline probe sweeps

    ex = Explorer(knob_space(cfg, shape.kind), max_passes=2)
    print(f"[hillclimb] {arch} {shape_name}: baseline eval...", flush=True)
    res = ex.global_search(objective, DEFAULT_TUNABLES)
    base = trace[0]

    print(f"[hillclimb] best est={res.cost:.3f}s after {res.evaluations} "
          f"evals; verifying with full compile...", flush=True)
    rec = lower_cell(arch, shape_name, multi_pod=multi_pod, tun=res.best,
                     oc=oc, verbose=False)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    out = OUT_ROOT / mesh_name / f"{arch}__{shape_name}__opt.json"
    rec["hillclimb"] = {
        "baseline": base, "best": res.best.as_dict(),
        "best_est_s": res.cost, "evaluations": res.evaluations,
        "trace": trace,
    }
    out.write_text(json.dumps(rec, indent=1))
    temp = rec["memory"].get("temp_size_in_bytes") or 0
    print(f"[hillclimb] {arch} {shape_name}: "
          f"{base['est_s']:.3f}s -> {res.cost:.3f}s "
          f"({base['est_s']/max(res.cost,1e-12):.2f}x), "
          f"temp={temp/1e9:.1f}GB (budget {HBM_BUDGET/1e9:.0f}GB), "
          f"evals={res.evaluations}", flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--shape", choices=list(SHAPES), required=True)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args(argv)
    hillclimb(args.arch, args.shape, multi_pod=args.multi_pod)


if __name__ == "__main__":
    main()
