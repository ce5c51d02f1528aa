"""Tiny cells for the benchmark's own tests on the CPU.

``tiny_root(path)`` lays out a folder shaped like ``benchmarks/chip/``: the
real references, metric readers and traffic mixes, plus configurations cut
to a few units of every width, a short mix, and a cell for each family.
``run_tiny`` drives ``run.main`` over it with the look for a chip replaced.
"""
from __future__ import annotations

import contextlib
import importlib.util
import io
import json
import os
import shutil

from chipbench import registry

CHIP = registry.ROOT
TINY_CELLS = ("tiny-qwen2.tinychat", "tiny-mamba2.tinychat")


def load_run():
    """``benchmarks/chip/run.py`` as a module of its own name."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(CHIP, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_root(path: str) -> dict:
    """Fill ``path`` with tiny cells; return the BENCHMARK.json object."""
    for kind in ("reference", "metrics", "traffic"):
        shutil.copytree(os.path.join(CHIP, kind), os.path.join(path, kind))
    for kind in ("configs", "cells"):
        os.makedirs(os.path.join(path, kind))
    q = registry.config("qwen2-1.5b")
    q["name"] = "tiny-qwen2"
    q["program"].update(name="tiny-qwen2", n_layers=2, d_model=64, n_heads=4,
                        n_kv_heads=2, d_ff=128, vocab=512, dtype="float32")
    q["check"]["logit_gap"] = 1e-3
    m = registry.config("mamba2-1.3b")
    m["name"] = "tiny-mamba2"
    m["program"].update(name="tiny-mamba2", n_layers=2, d_model=64,
                        vocab=512, dtype="float32")
    m["program"]["ssm"].update(d_state=16, head_dim=16, chunk=32)
    m["check"]["logit_gap"] = 1e-3
    for c in (q, m):
        _write(os.path.join(path, "configs", c["name"] + ".json"), c)
    _write(os.path.join(path, "traffic", "tinychat.json"), {
        "name": "tinychat", "arrivals": "poisson",
        "phases": [{"name": "c", "rate_knee_share": 0.8,
                    "prompt_len": {"32": 0.5, "64": 0.5},
                    "output_len": {"median": 8, "sigma": 0.5, "min": 4,
                                   "max": 16}}]})
    bench = registry.benchmark()
    bench["workloads"] = []
    for name in TINY_CELLS:
        config, mix = name.split(".")
        _write(os.path.join(path, "cells", name + ".json"), {
            "name": name, "config": config, "traffic": mix, "knee_rps": 20.0,
            "initial_tunables": {"cache_len": 16},
            "plan_space": {"serve_batch": [2, 4], "cache_len": [16]},
            "kermit": {"window_size": 4, "analysis_interval": 3,
                       "min_windows": 3, "drift_eps": 0.45,
                       "warmup_windows": 8}})
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1, "why": "test"})
    return bench


def cpu_stamp(chips: int) -> dict:
    return {"platform": "cpu", "kind": "cpu", "count": 1}


def run_tiny(root: str, bench: dict, workload: str, *, seed=3, seconds=2.0,
             trace=0, corrupt=None) -> tuple:
    """(last stdout line as a dict, all stdout, all stderr) of one run."""
    mod = load_run()
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = mod.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, bench=bench, stamp=cpu_stamp,
                      corrupt=corrupt)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), out.getvalue(), err.getvalue()
