"""Tiny cells for the benchmark's own tests on the CPU.

``tiny_root(path)`` lays out a folder shaped like ``benchmarks/chip/``: the
real references, counts, metric readers and traffic mixes, a short mix,
and for every configuration file a tiny cell of that mix.  Each
configuration is cut to a few units of every width by its own ``tiny``
block (its name, the overrides of its ``program`` block and its ``check``
limits), so a configuration added as a file gets its tiny cell, and the
tests parametrised over ``TINY_CELLS`` run it, with no edit here.
``run_tiny`` drives ``run.main`` over such a folder with the look for a
chip replaced.
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
MIX = "tinychat"


def tiny_config(config: dict) -> dict:
    """A configuration cut by its ``tiny`` block: a nested group of the
    ``program`` block (``ssm``) is updated key by key."""
    t = config["tiny"]
    out = {k: v for k, v in config.items() if k != "tiny"}
    program = dict(config["program"], name=t["name"])
    for k, v in t["program"].items():
        program[k] = dict(program[k], **v) if isinstance(v, dict) else v
    out.update(name=t["name"], program=program,
               check=dict(config["check"], **t["check"]))
    return out


def tiny_configs(source: str = CHIP) -> list:
    """Every configuration file under ``source``, cut by its ``tiny``
    block, in the order of their names."""
    return [tiny_config(registry.config(n, source))
            for n in registry.config_names(source)]


def tiny_cells(source: str = CHIP) -> tuple:
    return tuple(f"{c['name']}.{MIX}" for c in tiny_configs(source))


TINY_CONFIGS = tuple(c["name"] for c in tiny_configs())
TINY_CELLS = tiny_cells()


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


def tiny_root(path: str, source: str = CHIP) -> dict:
    """Fill ``path`` with the tiny cells of the configuration files under
    ``source``; return the BENCHMARK.json object that lists them."""
    for kind in ("reference", "counts", "metrics", "traffic"):
        shutil.copytree(os.path.join(source, kind), os.path.join(path, kind))
    for kind in ("configs", "cells"):
        os.makedirs(os.path.join(path, kind))
    _write(os.path.join(path, "traffic", MIX + ".json"), {
        "name": MIX, "arrivals": "poisson",
        "phases": [{"name": "c", "rate_knee_share": 0.8,
                    "prompt_len": {"32": 0.5, "64": 0.5},
                    "output_len": {"median": 8, "sigma": 0.5, "min": 4,
                                   "max": 16}}]})
    bench = registry.benchmark()
    bench["workloads"] = []
    for c in tiny_configs(source):
        _write(os.path.join(path, "configs", c["name"] + ".json"), c)
        name = f"{c['name']}.{MIX}"
        _write(os.path.join(path, "cells", name + ".json"), {
            "name": name, "config": c["name"], "traffic": MIX,
            "knee_rps": 20.0, "initial_tunables": {"cache_len": 16},
            "plan_space": {"serve_batch": [2, 4], "cache_len": [16]},
            "kermit": {"window_size": 4, "analysis_interval": 3,
                       "min_windows": 3, "drift_eps": 0.45,
                       "warmup_windows": 8}})
        bench["workloads"].append({"name": name, "config": c["name"],
                                   "traffic": MIX, "chips": 1,
                                   "why": "test"})
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
