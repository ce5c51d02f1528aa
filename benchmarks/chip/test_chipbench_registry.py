"""Every part of the benchmark is a file found by name, ``BENCHMARK.json``
keeps to its contract, and a part added as a new file is found and run
without an edit to any file already there."""
import hashlib
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import check, registry, report, testing  # noqa: E402

BENCH = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_part_is_a_file_found_by_name():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        cell = registry.cell(w["name"])
        assert (cell["config"], cell["traffic"]) == (w["config"],
                                                     w["traffic"])
        registry.traffic(cell["traffic"])
        cfg = registry.config(cell["config"])
        entry = configs[cell["config"]]
        assert os.path.samefile(
            os.path.join(HERE, "..", "..", entry["file"]),
            os.path.join(HERE, "configs", cfg["name"] + ".json"))
        assert entry["source"] == cfg["source"]
        assert entry["reduced"] == cfg["reduced"]
        assert callable(registry.reference(cfg["reference"]).logits_at)
        # a listed cell can be judged: every number compared has a limit
        for n in check.NUMBERS:
            limit = cfg["check"].get(n)
            assert isinstance(limit, (int, float)) and not isinstance(
                limit, bool) and limit >= 0, (w["name"], n, limit)
    for m in BENCH["per_layer"]:
        assert callable(registry.metric(m["name"]).read)


def test_end_to_end_names_are_the_ones_computed():
    import numpy as np
    from types import SimpleNamespace
    run = {"seg": SimpleNamespace(t_done=np.array([1.0, 2.0]),
                                  ttft=np.array([0.5, 0.7]),
                                  tpot=np.array([0.01, 0.02])),
           "window": SimpleNamespace(gen=np.array([3, 5])), "setup_s": 9.0}
    e2e = report.end_to_end(run)
    assert set(e2e) == {m["name"] for m in BENCH["end_to_end"]}
    assert e2e["output_tokens_per_s"] == 10 / 2.0


@pytest.mark.parametrize("bad", ["../x", "a/b", "", ".hidden", "a b"])
def test_bad_names_are_refused(bad):
    with pytest.raises(ValueError):
        registry.cell(bad)


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_added_parts_need_no_edit(tmp_path, monkeypatch):
    """A new configuration, mix, cell and metric, each added as a file,
    are found and run, and no file that was there changes."""
    import repro.runtime.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(report, "peak", lambda kind: None)
    root = str(tmp_path)
    bench = testing.tiny_root(root)
    before = _digests(root)

    cfg = registry.config("tiny-qwen2", root)
    cfg["name"] = "tiny-qwen2-wide"
    cfg["program"].update(name="tiny-qwen2-wide", d_ff=192)
    mix = registry.traffic("tinychat", root)
    mix["name"] = "tinylong"
    mix["phases"][0]["prompt_len"] = {"64": 1.0}
    cell = registry.cell("tiny-qwen2.tinychat", root)
    cell.update(name="tiny-qwen2-wide.tinylong", config="tiny-qwen2-wide",
                traffic="tinylong")
    for kind, obj in (("configs", cfg), ("traffic", mix), ("cells", cell)):
        with open(os.path.join(root, kind, obj["name"] + ".json"), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(root, "metrics", "calls_served.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.calls))\n")
    bench["workloads"].append({"name": cell["name"], "config": cfg["name"],
                               "traffic": "tinylong", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "calls_served", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "Engine", "moves": "ttft_p90_s"})

    line, _, _ = testing.run_tiny(root, bench, cell["name"], trace=1)
    assert line["correct"] is True
    assert line["metrics"]["calls_served"]["value"] >= 1
    after = _digests(root)
    assert {p: after[p] for p in before} == before


def test_every_configuration_has_a_tiny_cut():
    """Each configuration file cuts itself to a tiny cell: a name of its
    own, overrides of keys its program block has, and a limit for every
    number compared."""
    names = registry.config_names()
    assert {c["name"] for c in BENCH["configs"]} <= set(names)
    tiny = testing.tiny_configs()
    assert len({c["name"] for c in tiny}) == len(names)
    assert testing.TINY_CELLS == tuple(c["name"] + ".tinychat"
                                       for c in tiny)
    for n, t in zip(names, tiny):
        cfg = registry.config(n)
        assert NAME.match(t["name"]) and t["name"] not in names
        assert set(cfg["tiny"]["program"]) <= set(cfg["program"])
        for k, v in cfg["tiny"]["program"].items():
            if isinstance(v, dict):
                assert set(v) <= set(cfg["program"][k])
        assert set(t["program"]) == set(cfg["program"])
        for x in check.NUMBERS:
            assert isinstance(t["check"][x], float) and t["check"][x] > 0
        assert cfg["published_weight_bytes"] > 0
        assert "tiny" not in t


def test_a_configuration_added_as_files_runs_on_the_cpu(tmp_path,
                                                        monkeypatch):
    """A configuration file with its tiny block, a reference and the
    counts file of its family, each added as a file, give a tiny cell that
    runs through ``run.main`` correct, and not correct with its served
    tokens altered, with no edit to a file that was there."""
    import shutil
    import jax
    from chipbench import shapes
    import repro.runtime.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    src, out = tmp_path / "src", tmp_path / "out"
    for kind in ("configs", "reference", "counts", "metrics", "traffic"):
        shutil.copytree(os.path.join(HERE, kind), src / kind)
    # a new family name, which the program serves as a decoder with
    # attention, with a counts file and a reference of its own
    shutil.copy(src / "counts" / "dense.py", src / "counts" / "toy.py")
    shutil.copy(src / "reference" / "qwen2.py", src / "reference" / "toy.py")
    cfg = registry.config("qwen2-1.5b")
    cfg.update(name="toy-1b", reference="toy")
    cfg["program"].update(name="toy-1b", family="toy", d_ff=4096)
    cfg["tiny"].update(name="tiny-toy")
    cfg["tiny"]["program"]["d_ff"] = 96
    with open(src / "configs" / "toy-1b.json", "w") as f:
        json.dump(cfg, f)
    before = _digests(str(src))

    assert "tiny-toy.tinychat" in testing.tiny_cells(str(src))
    os.makedirs(out)
    bench = testing.tiny_root(str(out), str(src))
    assert "tiny-toy.tinychat" in {w["name"] for w in bench["workloads"]}
    p = registry.config("tiny-toy", str(out))["program"]
    ref = registry.reference("toy", str(out))
    tree = jax.eval_shape(lambda: ref.make_params(p, jax.random.PRNGKey(0)))
    assert shapes.at(str(out)).weight_bytes(p) == sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))
    line, _, _ = testing.run_tiny(str(out), bench, "tiny-toy.tinychat")
    assert line["correct"] is True

    def alter_token(engine):
        serve = engine.serve

        def altered(**kw):
            rep = serve(**kw)
            rep.generated = rep.generated.copy()
            rep.generated[:, -1] = (rep.generated[:, -1] + 1) \
                % engine.cfg.vocab
            return rep
        engine.serve = altered
    line, _, _ = testing.run_tiny(str(out), bench, "tiny-toy.tinychat",
                                  corrupt=alter_token)
    assert line["correct"] is False
    assert _digests(str(src)) == before
