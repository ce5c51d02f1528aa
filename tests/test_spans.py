"""The program's spans (``repro.runtime.spans``): off by default, nested
with parent ids and self time when on, compiles and GC pauses charged to
the innermost open span, and the engine's and the loop's reported times
taken from the same clock reads as their spans."""
import gc
import glob
import sys
import threading

import numpy as np
import pytest

from repro.configs.base import Tunables
from repro.kermit.serving import ServeEngine, tiny_config
from repro.runtime import spans

INITIAL = Tunables(serve_batch=2, cache_len=16)


@pytest.fixture
def log():
    """Recording on for one test, and off again whatever it does."""
    log = spans.enable()
    try:
        yield log
    finally:
        spans.disable()


@pytest.fixture(scope="module")
def engine():
    return ServeEngine(tiny_config("qwen2-1.5b"), seed=0, initial=INITIAL)


def by_name(log, name):
    i = spans.COLUMNS.index("name")
    return [dict(zip(spans.COLUMNS, r)) for r in log.records if r[i] == name]


def test_off_records_nothing_and_the_engine_still_reports(engine):
    assert spans.disable() is None
    with spans.span("kermit.x", a=1) as s:
        s.note(b=2)
    with spans.timed("kermit.y") as t:
        pass
    assert t.seconds >= 0.0
    rep = engine.serve(batch=2, prompt_len=8, gen=3, tunables=INITIAL)
    assert rep.prefill_s > 0.0 and rep.decode_s > 0.0
    assert spans.disable() is None


def test_nesting_parents_attributes_and_self_time(log):
    with spans.span("outer", batch=4) as outer:
        with spans.span("inner"):
            with spans.span("leaf"):
                pass
        with spans.timed("inner") as t:
            pass
        outer.note(clusters=3)
    o, = by_name(log, "outer")
    inner = by_name(log, "inner")
    leaf, = by_name(log, "leaf")
    assert o["parent"] is None and o["attrs"] == {"batch": 4, "clusters": 3}
    assert [s["parent"] for s in inner] == [o["id"], o["id"]]
    assert leaf["parent"] == inner[0]["id"]
    assert inner[1]["end_ns"] - inner[1]["start_ns"] == round(t.seconds * 1e9)
    # closed in the order they closed: the leaf first, the outer span last
    assert [r[2] for r in log.records] == ["leaf", "inner", "inner", "outer"]
    tot = log.totals()
    dur = o["end_ns"] - o["start_ns"]
    kids = sum(s["end_ns"] - s["start_ns"] for s in inner)
    assert tot["outer"]["count"] == 1 and tot["inner"]["count"] == 2
    assert tot["outer"]["seconds"] == pytest.approx(dur / 1e9)
    assert tot["outer"]["self_s"] == pytest.approx((dur - kids) / 1e9)
    # a window that starts after the outer span leaves it out
    assert "outer" not in log.totals(lo=o["start_ns"] / 1e9 + 1e-9)
    json_ = log.to_json()
    assert json_["columns"] == list(spans.COLUMNS) and len(json_["spans"]) == 4


def test_spans_on_two_threads_nest_per_thread(log):
    """Two threads open and close spans in lockstep, interleaved: each span's
    parent is the innermost span of its own thread, and each outer span's
    self time leaves out only its own thread's children."""
    step = threading.Barrier(2)

    def work(tag):
        with spans.span(f"{tag}.outer"):
            step.wait()
            with spans.span(f"{tag}.inner"):
                step.wait()
            step.wait()

    other = threading.Thread(target=work, args=("b",))
    other.start()
    work("a")
    other.join(30)
    assert not other.is_alive()
    rec = {r[2]: dict(zip(spans.COLUMNS, r)) for r in log.records}
    assert len(rec) == 4 and len({r["id"] for r in rec.values()}) == 4
    for tag in ("a", "b"):
        outer, inner = rec[f"{tag}.outer"], rec[f"{tag}.inner"]
        assert outer["parent"] is None
        assert inner["parent"] == outer["id"]
        assert outer["child_ns"] == inner["end_ns"] - inner["start_ns"]


def test_spans_from_many_threads_lose_no_update():
    """More threads than cores, switching as often as the interpreter
    allows, into a log too small for them all: every span is recorded or
    counted as dropped, once, under an id of its own."""
    threads, per = 16, 200
    log = spans.enable(capacity=threads * per // 2)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with spans.span("outer"):
                    with spans.span("inner"):
                        pass

        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
        spans.disable()
    assert len(log.records) == log.capacity
    assert len(log.records) + log.dropped == 2 * threads * per
    assert len({r[0] for r in log.records}) == len(log.records)


def test_a_full_log_counts_what_it_drops():
    log = spans.enable(capacity=3)
    try:
        for _ in range(5):
            with spans.span("s"):
                pass
    finally:
        spans.disable()
    assert len(log.records) == 3 and log.dropped == 2


def test_a_compile_is_charged_to_the_innermost_span(log):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 7.0 + 3.0)
    with spans.span("outer"):
        with spans.span("compiling"):
            f(jnp.arange(5.0)).block_until_ready()
    c, = by_name(log, "compiling")
    o, = by_name(log, "outer")
    assert c["compiles"] >= 1 and c["compile_ns"] > 0
    assert o["compiles"] == 0
    assert log.totals()["compiling"]["compiles"] == c["compiles"]


def test_a_gc_pause_is_charged_to_the_innermost_span(log):
    with spans.span("collecting"):
        gc.collect()
    s, = by_name(log, "collecting")
    assert s["gc_ns"] > 0
    assert log.totals()["collecting"]["gc_s"] == s["gc_ns"] / 1e9


def test_engine_times_are_its_spans(engine, log):
    rep = engine.serve(batch=2, prompt_len=8, gen=[5, 2], tunables=INITIAL)
    serve, = by_name(log, "kermit.serve")
    prefill, = by_name(log, "kermit.prefill")
    decode, = by_name(log, "kermit.decode")
    assert rep.prefill_s == (prefill["end_ns"] - prefill["start_ns"]) / 1e9
    assert rep.decode_s == (decode["end_ns"] - decode["start_ns"]) / 1e9
    assert serve["attrs"] == {"batch": 2, "prompt_len": 8,
                              "capacity": rep.capacity, "steps": 5}
    steps = by_name(log, "kermit.decode_step")
    assert len(steps) == rep.steps == 5
    assert all(s["parent"] == decode["id"] for s in steps)
    grow, = by_name(log, "kermit.cache_grow")
    wait, = by_name(log, "kermit.decode_wait")
    collect, = by_name(log, "kermit.collect")
    assert grow["parent"] == prefill["id"] and wait["parent"] == decode["id"]
    assert {prefill["parent"], decode["parent"], collect["parent"]} == \
        {serve["id"]}
    assert collect["start_ns"] >= decode["end_ns"]


def test_spans_land_on_the_host_plane_of_a_profile(engine, tmp_path):
    """Under ``jax.profiler`` a span is a host event with its attributes,
    inside the annotation around it, as long as its copy in memory."""
    import jax
    log = spans.enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation("bench.window"):
            engine.serve(batch=2, prompt_len=8, gen=3, tunables=INITIAL)
        jax.profiler.stop_trace()
    finally:
        spans.disable()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    host = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
            for p in jax.profiler.ProfileData.from_file(path).planes
            if p.name == "/host:CPU" for line in p.lines
            for e in line.events]
    win, = [h for h in host if h[0] == "bench.window"]
    kermit = [h for h in host if h[0].startswith("kermit.")]
    assert sorted({h[0] for h in kermit}) == sorted(
        {r[2] for r in log.records})
    assert all(win[1] <= s and s + d <= win[1] + win[2]
               for _, s, d, _ in kermit)
    serve, = [h for h in kermit if h[0] == "kermit.serve"]
    assert serve[3]["steps"] == 3
    mem, = by_name(log, "kermit.serve")
    assert abs(serve[2] - (mem["end_ns"] - mem["start_ns"])) < 1e6


def test_loop_spans_and_the_numbers_they_feed():
    """A short closed loop over the simulator: each probe is a
    ``kermit.probe`` whose seconds sum to ``measure_seconds``, and each
    analysis's discover and train spans give its reported seconds."""
    from repro.kermit import (AnalysisConfig, EventKind, KermitConfig,
                              KermitSession, MonitorConfig,
                              SimulatorExecutor)
    ex = SimulatorExecutor([("dense_train", 8), ("moe_train", 8)],
                           window_size=8, seed=0)
    cfg = KermitConfig(monitor=MonitorConfig(window_size=8),
                       analysis=AnalysisConfig(interval=4, min_windows=4))
    session = KermitSession(cfg, executor=ex)
    seconds = []
    session.subscribe(EventKind.ANALYSIS,
                      lambda ev: seconds.append(ev.detail["seconds"]))
    log = spans.enable()
    try:
        session.run()
    finally:
        spans.disable()
    tot = log.totals()
    probes = by_name(log, "kermit.probe")
    assert probes and sum(p["end_ns"] - p["start_ns"] for p in probes) \
        / 1e9 == pytest.approx(ex.measure_seconds, rel=1e-12)
    assert sum(p["attrs"]["candidates"] for p in probes) == ex.measured
    assert tot["kermit.step_batch"]["count"] == 1
    assert tot["kermit.monitor"]["count"] >= 1 and tot["kermit.plan"]
    analyses = by_name(log, "kermit.analyse")
    assert len(analyses) == len(seconds) >= 1
    for a, secs in zip(analyses, seconds):
        parts = [r for r in log.records if r[1] == a["id"]]
        assert {r[2] for r in parts} <= {"kermit.analyse.discover",
                                         "kermit.analyse.train"}
        assert sum(r[4] - r[3] for r in parts) / 1e9 == pytest.approx(
            secs, rel=1e-12)
        assert "clusters" in a["attrs"] and "windows" in a["attrs"]
    assert all(np.isfinite(t["seconds"]) for t in tot.values())
