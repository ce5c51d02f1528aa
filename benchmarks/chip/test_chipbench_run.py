"""One whole run of a tiny cell on the CPU, the look for a chip replaced:
the result line's schema, the traced line, and ``correct`` coming out
false when the timed path is broken underneath."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import registry, testing  # noqa: E402


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny"))
    return root, testing.tiny_root(root)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    # tests never turn JAX's persistent cache on
    import repro.runtime.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")


@pytest.mark.parametrize("workload", testing.TINY_CELLS)
def test_result_line(tiny, workload):
    root, bench = tiny
    line, out, err = testing.run_tiny(root, bench, workload, seed=2**31 + 7)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert list(line["checks"]) == ["logit_gap"]
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())
    # the numbers compared are the last lines on standard error too
    assert err.strip().splitlines()[-len(line["checks"]):] == [
        f"check {k}: {c['value']} (limit {c['limit']})"
        for k, c in line["checks"].items()]
    facts = [json.loads(x) for x in out.strip().splitlines()[:-1]]
    assert {"device", "loop_warmup", "loop_window", "compile_in_window",
            "backlog_at_close", "calls", "window_bytes_in_use_max"} <= {
                k for f in facts for k in f}


def test_traced_line(tiny, monkeypatch):
    from chipbench import report
    # no device metric is read on the CPU, so no peak is ever used
    monkeypatch.setattr(report, "peak", lambda kind: None)
    root, bench = tiny
    line, out, _ = testing.run_tiny(root, bench, "tiny-qwen2.tinychat",
                                    trace=1)
    facts = {k: v for x in out.strip().splitlines()[:-1]
             for k, v in json.loads(x).items()}
    assert set(facts["span_check"]) == {"serve", "step_batch"}
    names = {m["name"] for m in bench["per_layer"]}
    assert set(line["metrics"]) <= names
    # the CPU has no device plane: no device metric is read from it
    assert not {"prefill_mfu", "decode_mfu", "decode_roofline",
                "engine_idle_share", "decode_idle_share"} & set(
                    line["metrics"])
    assert {"prefill_ms_per_ktok", "decode_step_ms", "tpot_p90_s",
            "probe_s", "manager_self_s_per_window"} <= set(line["metrics"])
    # the program's spans, on in a traced run, are read
    assert {"engine_overhead_ms_per_call", "setup_probe_s",
            "setup_manager_s"} & names <= set(line["metrics"])
    assert facts["program_spans"]["dropped"] == 0
    assert len(facts["call_phases"]["rows"]) == len(facts["calls"]["rows"])
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "checks"


def alter_token(engine):
    serve = engine.serve

    def altered(**kw):
        rep = serve(**kw)
        rep.generated = np.array(rep.generated)
        rep.generated[:, -1] = (rep.generated[:, -1] + 1) % engine.cfg.vocab
        return rep
    engine.serve = altered


def cache_unchanged(engine):
    """The decode step returns the cache it was given."""
    import jax
    from repro.train.step import make_serve_step

    def frozen(tun):
        fn = jax.jit(make_serve_step(engine.cfg, tun))
        return lambda params, cache, batch: (fn(params, cache, batch)[0],
                                             cache)
    engine.decode_step = frozen


@pytest.mark.parametrize("workload", testing.TINY_CELLS)
@pytest.mark.parametrize("fault", [alter_token, cache_unchanged])
def test_broken_timed_path_is_not_correct(tiny, fault, workload):
    # the state-space cell's cache is its recurrent state
    root, bench = tiny
    line, _, _ = testing.run_tiny(root, bench, workload, corrupt=fault)
    assert line["correct"] is False
    c = line["checks"]["logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("workload", testing.TINY_CELLS)
def test_every_probe_shape_is_warm(tiny, workload):
    """A Plan probe of any batch, prompt and output length the cell's
    traffic holds finds its shape warmed in set-up, so it replays once."""
    from chipbench import traffic as T
    from chipbench.cell import Cell
    root, _ = tiny
    cell = Cell(workload, root)
    cell.build_engine(5)
    ex, session = cell.session()
    try:
        lo, hi = T.output_range(cell.mix)
        space = cell.cell["plan_space"]
        for B in space["serve_batch"]:
            for cache_len in space.get("cache_len", [0]):
                tun = cell.tunables(serve_batch=B, cache_len=cache_len)
                for P in T.prompt_buckets(cell.mix):
                    for g in range(lo - 1, hi):
                        cap = cell.engine.capacity_for(P, g, tun)
                        assert (tun, B, P, cap) in ex._warm, (B, P, g)
    finally:
        session.close()


def test_no_chip_no_result():
    run = testing.load_run()
    with pytest.raises(SystemExit):
        run.device_stamp(1)


def test_bare_checkout_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    prints no result and exits nonzero."""
    import shutil
    repo = os.path.dirname(os.path.dirname(HERE))
    shutil.copy(os.path.join(repo, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen2-1.5b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
