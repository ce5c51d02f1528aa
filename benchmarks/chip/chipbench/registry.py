"""Find the benchmark's parts by name, each in a file of its own:

  configs/<name>.json     a model configuration, with its source, and a
                          ``tiny`` block: the overrides of its ``program``
                          block and its ``check`` limits that cut it to a
                          cell the tests run on the CPU (``testing.py``;
                          ``run.py`` never reads it)
  reference/<module>.py   the plain reference a configuration names
  counts/<family>.py      the operations and bytes of a model family
                          (``shapes.py``), by the ``program`` block's
                          ``family``
  traffic/<mix>.json      a traffic mix
  cells/<cell>.json       a cell: configuration, mix, rate, loop settings
  metrics/<metric>.py     a per-layer metric: ``read(run) -> float | None``

Adding one of these is adding a file; nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME_CHARS = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                 "0123456789_.-")


def _checked(name: str) -> str:
    if not name or not set(name) <= NAME_CHARS or name[0] in ".-":
        raise ValueError(f"bad name {name!r}")
    return name


def _json(root: str, kind: str, name: str) -> dict:
    path = os.path.join(root, kind, _checked(name) + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    with open(path) as f:
        d = json.load(f)
    if d.get("name") != name:
        raise ValueError(f"{path} names itself {d.get('name')!r}")
    return d


def _module(root: str, kind: str, name: str):
    path = os.path.join(root, kind, _checked(name) + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module named {name!r} ({path})")
    folder = os.path.dirname(path)
    if folder not in sys.path:            # siblings import one another
        sys.path.insert(0, folder)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name: str, root: str = ROOT) -> dict:
    return _json(root, "configs", name)


def traffic(name: str, root: str = ROOT) -> dict:
    return _json(root, "traffic", name)


def cell(name: str, root: str = ROOT) -> dict:
    return _json(root, "cells", name)


def reference(name: str, root: str = ROOT):
    return _module(root, "reference", name)


def metric(name: str, root: str = ROOT):
    return _module(root, "metrics", name)


def counts(family: str, root: str = ROOT):
    return _module(root, "counts", family)


def config_names(root: str = ROOT) -> list:
    """Every configuration file's name, sorted."""
    return sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(root, "configs")) if f.endswith(".json"))


def benchmark(root: str = ROOT) -> dict:
    """``BENCHMARK.json`` at the root of the checkout."""
    path = os.path.join(root, "..", "..", "BENCHMARK.json")
    with open(path) as f:
        return json.load(f)
