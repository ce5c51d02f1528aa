"""The program's spans as the benchmark reads them
(``chipbench.program_spans`` and the readers that use it): idle gaps named
by the innermost span of either kind, with the gaps themselves unchanged;
the in-memory log on the window's clock; the fact lines; each reader on a
hand-made view; and one tiny run on the CPU with the spans on."""
import glob
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import program_spans as PS  # noqa: E402
from chipbench import registry, testing, trace as TR  # noqa: E402

MS = 1_000_000


def events():
    """Device programs and the harness's spans, as in the trace tests."""
    return TR.Events(
        device=[("jit_prefill_step", 1 * MS, 3 * MS, 0),
                ("jit_serve_step", 4 * MS, 5 * MS, 0),
                ("jit_serve_step", 5 * MS, 6 * MS, 0),
                ("jit_prefill_step", 12 * MS, 13 * MS, 0),
                ("jit_convert", 19 * MS, 22 * MS, 0)],
        span=[("bench.window", 0, 20 * MS),
              ("bench.serve", 0, 7 * MS),
              ("bench.wait", 7 * MS, 10 * MS),
              ("bench.step_batch", 10 * MS, 15 * MS),
              ("bench.probe", 11 * MS, 14 * MS),
              ("bench.serve", 15 * MS, 20 * MS)],
        op_s={"fusion.1": 0.002})


def program():
    """The engine's spans inside the two serve calls: idle 0-1 ms lies in
    the prefill, 3-4 ms in the decode loop, 13-19 ms (middle 16) in the
    collect of the second call; 6-12 ms (middle 9) in no program span."""
    return [("kermit.serve", MS // 10, 69 * MS // 10),
            ("kermit.prefill", MS // 5, 32 * MS // 10),
            ("kermit.decode", 33 * MS // 10, 62 * MS // 10),
            ("kermit.serve", 151 * MS // 10, 199 * MS // 10),
            ("kermit.collect", 155 * MS // 10, 17 * MS),
            ("kermit.serve", 25 * MS, 26 * MS)]         # after the window


def test_gaps_named_by_the_innermost_span_of_either_kind():
    ev = events()
    plain = TR.reduce(ev)
    r = PS.reduce(ev, program())
    # the same gaps, longest first, and the harness's own names where no
    # program span holds a gap's middle
    assert [s for _, s in r.idle_gaps] == [s for _, s in plain.idle_gaps]
    assert sorted(n for n, _ in r.idle_gaps) == [
        "kermit.collect", "kermit.decode", "kermit.prefill", "wait"]
    assert dict((n, s) for n, s in r.idle_gaps)["kermit.collect"] == \
        pytest.approx(0.006)
    assert sorted(n for n, _ in plain.idle_gaps) == [
        "serve", "serve", "serve", "wait"]
    assert r.program_span_s["kermit.decode"] == pytest.approx(0.0029)
    assert r.busy_in_program_span_s["kermit.decode"] == pytest.approx(0.002)
    assert r.program_span_s["kermit.serve"] == pytest.approx(0.0116)
    assert len(r.spans) == 5            # the span after the close is out
    # reducing with the program's spans leaves the harness's reduction
    assert TR.reduce(ev) == plain


def test_recorded_chip_trace_reduces_as_before():
    with open(os.path.join(HERE, "testdata", "trace_events.json")) as f:
        ev = TR.Events.from_json(json.load(f))
    plain = TR.reduce(ev)
    r = PS.reduce(ev, [])
    assert r.idle_gaps == plain.idle_gaps
    assert r.program_span_s == {} and r.spans == []


def log_json(rows):
    """A ``SpanLog.to_json()`` from (id, parent, name, start_ms, end_ms)."""
    from repro.runtime import spans
    child = {}
    for i, p, _, s, e in rows:
        child[p] = child.get(p, 0) + (e - s) * MS
    return {"columns": list(spans.COLUMNS), "dropped": 0,
            "outside": {"compiles": 0, "compile_ns": 0, "gc_ns": 0},
            "spans": [[i, p, n, s * MS, e * MS, child.get(i, 0), 0, 0, 0,
                       None] for i, p, n, s, e in rows]}


# set-up before 1000 ms, the window from 1000 ms: one search in set-up,
# one engine call in the window
LOG = [(0, None, "kermit.step_batch", 100, 160),
       (1, 0, "kermit.monitor", 100, 102),
       (2, 0, "kermit.plan", 105, 158),
       (3, 2, "kermit.probe", 106, 130),
       (4, 3, "kermit.serve", 107, 129),
       (5, 2, "kermit.probe", 131, 150),
       (10, None, "kermit.serve", 1002, 1050),
       (11, 10, "kermit.prefill", 1002.5, 1010),
       (12, 11, "kermit.cache_grow", 1003, 1004),
       (13, 10, "kermit.decode", 1011, 1046),
       (14, 13, "kermit.decode_step", 1011, 1012),
       (15, 13, "kermit.decode_step", 1012, 1014),
       (16, 13, "kermit.decode_wait", 1014, 1045),
       (17, 10, "kermit.collect", 1046, 1049),
       (20, None, "kermit.step_batch", 1051, 1052)]


def spans_view(seconds=10.0):
    return PS.Spans(log_json(LOG), t0=1.0, seconds=seconds)


def test_spans_on_the_window_clock():
    sp = spans_view()
    b, = sp.named("kermit.step_batch", hi=0.0)
    assert b.start == pytest.approx(-0.9) and b.seconds == pytest.approx(0.06)
    assert [s.id for s in sp.within(b, "kermit.probe")] in ([3, 5], [5, 3])
    assert [c.id for c in sp.calls()] == [10]      # not the probe's call
    tot = sp.totals(hi=0.0)
    assert tot["kermit.plan"]["self_s"] == pytest.approx(0.053 - 0.043)
    assert tot["kermit.probe"]["count"] == 2
    assert "kermit.decode" in sp.totals(0.0, 10.0)
    # the window's start found again from the call's dispatch time
    assert PS.align(log_json(LOG), [0.0015]) == pytest.approx(1.0005)


def test_call_phases():
    got = PS.call_phases(spans_view())
    assert got["columns"] == PS.PHASES
    row, = got["rows"]
    want = [0.002, 0.5, 1.0, 6.0, 3.0, 31.0, 3.0, 48 - 7.5 - 35 - 3, 0, 2]
    assert row[:8] == pytest.approx(want[:8])
    assert 1.0 < row[8] <= 2.0 and row[9] == 2


def test_span_check_pairs():
    sp = spans_view()
    call = SimpleNamespace(t_dispatch=0.0015, t_end=0.0505, prefill_s=0.0075,
                           decode_s=0.035)
    step = SimpleNamespace(t_start=0.0509, wall_s=0.00101)
    run = {"seg": SimpleNamespace(calls=[call], steps=[step]),
           "traced": (0.0, 10.0)}
    got = PS.span_check(sp, run)
    assert got["report"]["calls"] == [1, 1]
    assert got["report"]["prefill_max_abs_s"] == pytest.approx(0, abs=1e-12)
    assert got["report"]["decode_max_abs_s"] == pytest.approx(0, abs=1e-12)
    assert got["host_clock"]["serve"] == {"program_s": pytest.approx(0.048),
                                          "host_s": pytest.approx(0.049)}
    assert got["host_clock"]["step_batch"]["program_s"] == \
        pytest.approx(0.001)
    trace = PS.Reduced({}, {}, [], [("kermit.collect", 46 * MS, 49 * MS + 7)])
    got = PS.span_check(sp, run, trace)["trace"]["kermit.collect"]
    assert got["count"] == [1, 1]
    assert got["max_abs_s"] == pytest.approx(7e-9)


def test_readers_on_a_hand_made_view():
    read = {n: registry.metric(n).read for n in (
        "decode_idle_share", "engine_overhead_ms_per_call", "setup_probe_s",
        "setup_manager_s")}
    red = TR.reduce(events())
    pr = PS.reduce(events(), program())
    red.program_span_s = pr.program_span_s
    red.busy_in_program_span_s = pr.busy_in_program_span_s
    view = SimpleNamespace(trace=red, spans=spans_view())
    assert read["decode_idle_share"](view) == pytest.approx(
        100 * (1 - 2 / 2.9))
    assert read["engine_overhead_ms_per_call"](view) == pytest.approx(
        48 - 7.5 - 35)
    assert read["setup_probe_s"](view) == pytest.approx(0.043)
    assert read["setup_manager_s"](view) == pytest.approx(0.060 - 0.043)
    # a run of a program without spans, or untraced: nothing to read
    bare = SimpleNamespace(trace=TR.reduce(events()), calls=[], steps=[])
    assert all(f(bare) is None for f in read.values())
    assert read["decode_idle_share"](SimpleNamespace(trace=None)) is None


def test_profiler_copy_of_the_engine_spans(tmp_path):
    """On the CPU, ``load`` finds the engine's spans on the host plane,
    inside the ``bench.window`` annotation, as long as their copies in
    memory to within a millisecond."""
    import jax
    from repro.configs.base import Tunables
    from repro.kermit.serving import ServeEngine, tiny_config
    from repro.runtime import spans
    tun = Tunables(serve_batch=2, cache_len=16)
    eng = ServeEngine(tiny_config("qwen2-1.5b"), seed=0, initial=tun)
    eng.serve(batch=2, prompt_len=8, gen=4, tunables=tun)
    log = spans.enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.serve(batch=2, prompt_len=8, gen=4, tunables=tun)
        jax.profiler.stop_trace()
    finally:
        spans.disable()
    program = PS.load(str(tmp_path))
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    win = [(e.start_ns, e.start_ns + e.duration_ns)
           for p in jax.profiler.ProfileData.from_file(path).planes
           for line in p.lines for e in line.events
           if e.name == "bench.window"]
    (lo, hi), = win
    assert sorted(n for n, *_ in program) == sorted(r[2] for r in log.records)
    assert all(lo <= s <= e <= hi for _, s, e in program)
    assert sum(1 for n, *_ in program if n == "kermit.decode_step") == 4
    mem = PS.Spans(log.to_json(), 0.0)
    for name in ("kermit.serve", "kermit.prefill", "kermit.decode"):
        (_, s, e), = [p for p in program if p[0] == name]
        m, = mem.named(name)
        assert abs((e - s) / 1e9 - m.seconds) < 1e-3


@pytest.fixture
def no_compile_cache(monkeypatch):
    import repro.runtime.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")


def test_tiny_run_with_the_spans_on(tmp_path, monkeypatch, no_compile_cache):
    """A whole run of a tiny cell with the program's spans on from before
    the cell is built: the facts and readers come out of its log."""
    from chipbench import report
    from repro.runtime import spans
    root = str(tmp_path)
    bench = testing.tiny_root(root)
    runs = []
    e2e = report.end_to_end
    monkeypatch.setattr(report, "end_to_end",
                        lambda run: runs.append(run) or e2e(run))
    log = spans.enable()
    try:
        line, _, _ = testing.run_tiny(root, bench, "tiny-qwen2.tinychat")
    finally:
        spans.disable()
    assert line["correct"] is True
    run, = runs
    # the segment's calls, those after the close included, are the last
    t0 = PS.align(log.to_json(), [c.t_dispatch for c in run["seg"].calls])
    calls = [c for c in run["seg"].calls if c.t_dispatch < run["seconds"]]
    sp = PS.Spans(log.to_json(), t0, run["seconds"])
    assert log.dropped == 0
    phases = PS.call_phases(sp)
    assert len(phases["rows"]) == len(calls)
    assert [r[-1] for r in phases["rows"]] == [c.steps for c in calls]
    check = PS.span_check(sp, run)
    assert check["report"]["calls"] == [len(calls)] * 2
    assert check["report"]["prefill_max_abs_s"] == 0.0
    assert check["report"]["decode_max_abs_s"] == 0.0
    hc = check["host_clock"]
    assert 0 < hc["serve"]["program_s"] <= hc["serve"]["host_s"]
    assert 0 < hc["step_batch"]["program_s"] <= hc["step_batch"]["host_s"]
    view = SimpleNamespace(trace=None, spans=sp)
    assert registry.metric("engine_overhead_ms_per_call").read(view) > 0
    # the loop searched in set-up, through the engine
    assert registry.metric("setup_probe_s").read(view) > 0
    assert registry.metric("setup_manager_s").read(view) > 0
    setup = sp.totals(hi=0.0)
    assert setup["kermit.probe"]["count"] >= 1
    assert np.isfinite(setup["kermit.step_batch"]["self_s"])


READERS = ("decode_idle_share", "engine_overhead_ms_per_call",
           "setup_probe_s", "setup_manager_s")


def test_traced_run_reads_the_program_spans(tmp_path, monkeypatch,
                                            no_compile_cache):
    """``--trace 1`` turns the program's spans on before set-up and off at
    the end: the readers get them as ``RunView.spans`` and in the reduced
    trace, and each of the four span readers returns a number.  The CPU
    has no device plane, so one is planted: a program over the first half
    of each ``kermit.decode`` span, which reads as half of the decode loop
    idle."""
    from chipbench import report
    from repro.runtime import spans
    root = str(tmp_path)
    bench = testing.tiny_root(root)
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] in READERS] + [
        {"name": n, "unit": "x", "better": "lower", "source": "program_span",
         "layer": "Engine", "moves": "ttft_p90_s"}
        for n in READERS if n not in {m["name"] for m in bench["per_layer"]}]
    load = TR.load_events

    def planted(trace_dir):
        ev = load(trace_dir)
        ev.device += [("jit_stand_in", s, (s + e) // 2, 0)
                      for n, s, e in PS.load(trace_dir)
                      if n == "kermit.decode"]
        return ev
    monkeypatch.setattr(TR, "load_events", planted)
    monkeypatch.setattr(report, "peak", lambda kind: None)
    views = []
    make = report.view
    monkeypatch.setattr(report, "view",
                        lambda *a: views.append(make(*a)) or views[-1])
    line, out, _ = testing.run_tiny(root, bench, "tiny-qwen2.tinychat",
                                    trace=1)
    assert spans._log is None                     # off again after the run
    assert line["correct"] is True
    view, = views
    assert isinstance(view.spans, PS.Spans) and view.spans.seconds == 2.0
    assert view.trace.program_span_s["kermit.decode"] > 0
    assert set(line["metrics"]) == set(READERS)
    assert all(np.isfinite(m["value"]) for m in line["metrics"].values())
    assert line["metrics"]["decode_idle_share"]["value"] == pytest.approx(
        50, abs=1)
    assert line["metrics"]["setup_probe_s"]["value"] > 0
    # set-up spans are in the log: the loop's search, and the warm-up
    assert view.spans.named("kermit.probe", hi=0.0)
    # the breakdown's gaps are named by the innermost span of either kind
    assert line["breakdown"]["idle_gaps"] == view.trace.idle_gaps


def test_untraced_run_records_no_spans(tmp_path, monkeypatch,
                                       no_compile_cache):
    from chipbench import report
    from repro.runtime import spans
    root = str(tmp_path)
    bench = testing.tiny_root(root)
    monkeypatch.setattr(spans, "enable", lambda *a: pytest.fail(
        "spans enabled in a run without the trace"))
    views = []
    make = report.view
    monkeypatch.setattr(report, "view",
                        lambda *a: views.append(make(*a)) or views[-1])
    line, _, _ = testing.run_tiny(root, bench, "tiny-qwen2.tinychat")
    assert line["correct"] is True and views == []
    assert spans._log is None
