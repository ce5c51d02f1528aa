"""The trace reduction: on hand-made events with known answers, and on a
small trace recorded on a TPU v5e chip (``testdata/trace_events.json``:
one warm engine call, then an idle wait, inside one ``bench.window``
span)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import trace as T  # noqa: E402

MS = 1_000_000


def hand_made():
    return T.Events(
        device=[("jit_prefill_step", 1 * MS, 3 * MS, 0),
                ("jit_serve_step", 4 * MS, 5 * MS, 0),
                ("jit_serve_step", 5 * MS, 6 * MS, 0),
                ("jit_prefill_step", 12 * MS, 13 * MS, 0),   # in the probe
                ("jit_convert", 19 * MS, 22 * MS, 0)],       # past the close
        span=[("bench.window", 0, 20 * MS),
              ("bench.serve", 0, 7 * MS),
              ("bench.wait", 7 * MS, 10 * MS),
              ("bench.step_batch", 10 * MS, 15 * MS),
              ("bench.probe", 11 * MS, 14 * MS),
              ("bench.serve", 15 * MS, 20 * MS)],
        op_s={"fusion.1": 0.002, "copy.2": 0.003})


def test_hand_made_events():
    r = T.reduce(hand_made())
    assert r.window_s == pytest.approx(0.020)
    # 2 + 1 + 1 + 1 + 1 ms busy inside the window, the last clipped at 20
    assert r.busy_s == pytest.approx(0.006)
    assert r.program_s["jit_prefill_step"] == pytest.approx(0.003)
    assert r.program_s["jit_convert"] == pytest.approx(0.001)
    # the probe's prefill ran inside step_batch, not inside a serve span
    assert r.program_in_serve_s == pytest.approx(
        {"jit_prefill_step": 0.002, "jit_serve_step": 0.002})
    assert r.span_s["serve"] == pytest.approx(0.012)
    assert r.busy_in_span_s["serve"] == pytest.approx(0.005)
    assert r.busy_in_span_s["wait"] == 0.0
    assert r.device_ops == [["copy.2", 0.003], ["fusion.1", 0.002]]
    # longest idle gaps first, each named by the innermost host span over
    # its middle: 6..12 ms lies in the wait, 13..19 ms in the second serve
    assert sorted(r.idle_gaps[:2]) == [["serve", pytest.approx(0.006)],
                                       ["wait", pytest.approx(0.006)]]
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(0.014)


def test_interval_helpers():
    assert T.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert T.intersect([(0, 4), (6, 9)], [(3, 7)]) == [(3, 4), (6, 7)]
    assert T.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]


def test_one_window_span_required():
    ev = hand_made()
    ev.span.append(("bench.window", 0, 5))
    with pytest.raises(RuntimeError):
        T.reduce(ev)


def test_recorded_chip_trace():
    """Events read by ``load_events`` from a trace taken on one TPU v5e: one
    ``ServeEngine.serve`` call of qwen2-1.5b (batch 8, prompt 512, 32 decode
    steps) in a ``bench.serve`` span, then 50 ms asleep in ``bench.idle``;
    the ``bench.window`` span was added around both, and the operation
    table kept to its 40 longest names."""
    with open(os.path.join(HERE, "testdata", "trace_events.json")) as f:
        ev = T.Events.from_json(json.load(f))
    r = T.reduce(ev)
    assert 0 < r.busy_s < r.window_s
    # both engine programs ran inside the serve span, 32 decode steps
    assert r.program_in_serve_s["jit_prefill_step"] > 0
    assert r.program_in_serve_s["jit_serve_step"] > \
        r.program_in_serve_s["jit_prefill_step"]
    assert sum(1 for n, *_ in ev.device if n == "jit_serve_step") == 32
    assert r.busy_in_span_s["idle"] == 0.0
    assert 0 < r.busy_in_span_s["serve"] <= r.span_s["serve"]
    assert r.idle_gaps[0][0] == "idle"
    assert sum(s for _, s in r.idle_gaps) <= r.window_s - r.busy_s + 1e-9
    assert len(r.device_ops) == 10


def test_span_check_sets_the_trace_beside_the_host_clock():
    """The trace's serve and step_batch spans against the host clock's
    seconds for the calls that started inside the trace."""
    from types import SimpleNamespace
    from chipbench import report
    r = T.reduce(hand_made())
    calls = [SimpleNamespace(t_dispatch=0.000, t_end=0.007),
             SimpleNamespace(t_dispatch=0.015, t_end=0.020),
             SimpleNamespace(t_dispatch=0.030, t_end=0.036)]  # after it
    steps = [SimpleNamespace(t_start=0.010, wall_s=0.005)]
    run = {"traced": (0.0, 0.020),
           "seg": SimpleNamespace(calls=calls, steps=steps)}
    got = report.span_check(run, r)
    assert got["serve"] == {"trace_s": pytest.approx(0.012),
                            "host_s": pytest.approx(0.012)}
    assert got["step_batch"] == {"trace_s": pytest.approx(0.005),
                                 "host_s": pytest.approx(0.005)}
