"""Autonomic serving: ServeExecutor + trace-driven traffic close the MAPE-K
loop around the real inference stack (PR 8 tentpole).

Covers the seeded traffic generator (bit-identical schedules), the serving
knobs' struct-of-arrays codec registration, counter-surface parity with
SimulatorExecutor, ServeEngine jit reuse, the nearest-rank percentile
helper, the end-to-end autonomous re-plan gate, and checkpoint/restore with
a ServeExecutor attached.
"""
import numpy as np
import pytest

from repro.configs.base import (Tunables, arrays_to_tunables,
                                tunables_to_arrays)
from repro.kermit import (AnalysisConfig, BatchExecutor, EventKind, Executor,
                          KermitConfig, KermitSession, KnowledgeConfig,
                          MonitorConfig, PlanConfig, SimulatorExecutor)
from repro.kermit.serving import (ServeConfig, ServeEngine, ServeExecutor,
                                  TrafficGenerator, run_serving_session,
                                  tiny_config)
from repro.runtime.telemetry import percentile

INITIAL = Tunables(serve_batch=4, cache_len=32)


@pytest.fixture(scope="module")
def engine():
    """One shared tiny engine — jit caches are keyed by Tunables, so tests
    sharing it only get faster, never entangled."""
    return ServeEngine(tiny_config("qwen2-1.5b"), seed=0, initial=INITIAL)


def _chat_executor(engine, n_windows=2, seed=0, **cfg_kw):
    traffic = TrafficGenerator.kway(("chat",), window_size=4, seed=seed,
                                    n_windows=n_windows, gap=1.0)
    cfg = ServeConfig(window_size=4, **cfg_kw) if cfg_kw else None
    return ServeExecutor(engine, traffic, config=cfg, initial=INITIAL)


# -- percentile helper (satellite) ------------------------------------------


def test_percentile_nearest_rank():
    v = np.arange(1, 101)                    # 1..100
    assert percentile(v, 50.0) == 50.0
    assert percentile(v, 99.0) == 99.0
    assert percentile(v, 100.0) == 100.0
    assert percentile(v, 0.0) == 1.0         # rank clamps to the minimum
    assert percentile([7.0], 99.0) == 7.0
    # deterministic: no interpolation, always an observed sample
    assert percentile([1.0, 2.0, 10.0], 66.0) == 2.0


def test_percentile_rejects_bad_input():
    with pytest.raises(ValueError):
        percentile([], 50.0)
    with pytest.raises(ValueError):
        percentile([1.0], 101.0)


# -- traffic generation ------------------------------------------------------


def test_traffic_same_seed_bit_identical():
    a = TrafficGenerator.diurnal(window_size=8, seed=3).schedule()
    b = TrafficGenerator.diurnal(window_size=8, seed=3).schedule()
    assert len(a) == len(b) == 32
    for wa, wb in zip(a, b):
        assert wa.index == wb.index and wa.phase == wb.phase
        for f in ("arrivals", "tenant", "prompt_len", "gen"):
            assert np.array_equal(getattr(wa, f), getattr(wb, f)), f
    c = TrafficGenerator.diurnal(window_size=8, seed=4).schedule()
    assert any(not np.array_equal(wa.arrivals, wc.arrivals)
               for wa, wc in zip(a, c))


def test_traffic_phase_boundaries():
    gen = TrafficGenerator.diurnal(window_size=4, night_windows=4,
                                   day_windows=6, seed=0)
    assert gen.phase_boundaries() == [4]
    assert gen.n_windows == 10
    sched = gen.schedule()
    assert [w.phase for w in sched] == ["night"] * 4 + ["day"] * 6
    assert all(w.gap == 4.0 for w in sched[:4])
    assert all(w.gap == 0.25 for w in sched[4:])


def test_kway_dirichlet_mix_varies_per_window():
    sched = TrafficGenerator.kway(("chat", "agent", "bulk"), window_size=32,
                                  seed=0, n_windows=8).schedule()
    hists = [tuple(np.bincount(w.tenant, minlength=3)) for w in sched]
    assert len(set(hists)) > 1, "Dirichlet mixing collapsed to one mix"
    assert all(w.phase == "kway" for w in sched)


def test_bursty_preserves_offered_load():
    gap = 1.0
    sched = TrafficGenerator.bursty(window_size=16, seed=0, n_windows=50,
                                    gap=gap, burstiness=0.5).schedule()
    gaps = np.concatenate([np.diff(np.concatenate([[0.0], w.arrivals]))
                           for w in sched])
    # burst compression is mean-preserving: same offered load, heavier tail
    assert abs(gaps.mean() - gap) < 0.2 * gap
    assert np.quantile(gaps, 0.25) < 0.2 * gap


# -- serving knobs in the struct-of-arrays codec (satellite) -----------------


def test_serving_knobs_codec_round_trip():
    ts = [Tunables(),
          Tunables(serve_batch=4, cache_len=32, prefill_chunk=16,
                   cache_dtype="bfloat16"),
          Tunables(serve_batch=2, cache_dtype="float32")]
    arrays = tunables_to_arrays(ts)
    for knob in ("serve_batch", "prefill_chunk", "cache_len", "cache_dtype"):
        assert knob in arrays, f"serving knob {knob} missing from codec"
        assert arrays[knob].dtype == np.int32
    assert arrays_to_tunables(arrays) == ts


# -- executor protocol + counter parity --------------------------------------


def test_counter_surface_parity_with_simulator(engine):
    sim = SimulatorExecutor([("dense_train", 1)], window_size=8, seed=0)
    srv = _chat_executor(engine)
    for ex in (sim, srv):
        assert isinstance(ex, Executor)
        assert isinstance(ex, BatchExecutor)
        ex.apply(INITIAL)
        ex.measure()
        costs = ex.measure_batch([INITIAL,
                                  INITIAL.replace(serve_batch=2)])
        assert len(costs) == 2 and all(np.isfinite(c) for c in costs)
    for counter in ("applied", "measured", "measured_batches"):
        assert getattr(sim, counter) == getattr(srv, counter), counter
    assert srv.measure_seconds > 0.0
    # the serving replay is a probe: pricing candidates never moves state
    assert srv.current == INITIAL
    state = srv.export_state()
    for key in ("applied", "measured", "measured_batches", "measure_seconds",
                "current", "cursor", "unit", "window_log"):
        assert key in state, key


def test_restore_accepts_a_snapshot_with_request_latencies(engine):
    """Snapshots written before the flat per-request latency list was
    dropped still restore; the list is not carried forward."""
    srv = _chat_executor(engine, n_windows=2)
    next(srv.telemetry_stream())
    state = srv.export_state()
    assert "request_latencies" not in state
    old = dict(state, request_latencies=[0.5, 0.25, 0.125, 1.0])
    back = _chat_executor(engine, n_windows=2)
    back.restore_state(old)
    assert back.export_state() == state
    assert not hasattr(back, "request_latencies")


def test_probe_cost_is_tail_aware(engine):
    srv = _chat_executor(engine, tail_weight=1.0)
    stats = srv.probe_stats(INITIAL)
    assert stats["cost"] == stats["p99"]
    srv2 = _chat_executor(engine, tail_weight=0.0)
    stats2 = srv2.probe_stats(INITIAL)
    assert stats2["cost"] == stats2["mean"]
    assert stats["p99"] >= stats["mean"] > 0.0


def test_engine_jit_reuse(engine):
    before = dict(engine.stats)
    rep1 = engine.serve(batch=4, prompt_len=16, gen=6, tunables=INITIAL)
    mid = dict(engine.stats)
    rep2 = engine.serve(batch=4, prompt_len=16, gen=6, tunables=INITIAL)
    after = dict(engine.stats)
    # second identical-shape call compiles nothing new
    assert after["prefill_builds"] == mid["prefill_builds"]
    assert after["decode_builds"] == mid["decode_builds"]
    assert mid["prefill_builds"] <= before["prefill_builds"] + 1
    for rep in (rep1, rep2):
        assert rep.capacity == 32                    # 16 + 6 rounds up to 32
        assert rep.completion_s.shape == (4,)
        assert rep.total_s >= float(rep.completion_s.max()) > 0.0
        assert rep.tokens == 4 * (6 + 1)             # gen + the prefill token
    # greedy decode on identical inputs is deterministic
    assert np.array_equal(rep1.generated, rep2.generated)


# -- the closed loop ---------------------------------------------------------


def _loop_config(space, initial):
    return KermitConfig(
        monitor=MonitorConfig(window_size=8),
        analysis=AnalysisConfig(interval=6, min_windows=6),
        knowledge=KnowledgeConfig(drift_eps=0.45),
        plan=PlanConfig(space=space, default_tunables=initial.as_dict()))


def test_autonomic_replan_on_traffic_phase_change():
    """The tentpole gate: diurnal night -> day traffic drifts the observed
    workload; the session detects it from telemetry alone, re-plans via the
    executor, and the committed config change lands in the day phase with
    p99 no worse than before — zero human calls."""
    initial = Tunables(serve_batch=8, cache_len=64)
    eng = ServeEngine(tiny_config("qwen2-1.5b"), seed=0, initial=initial)
    traffic = TrafficGenerator.diurnal(window_size=8, seed=0,
                                       night_windows=12, day_windows=12)
    ex = ServeExecutor(eng, traffic, config=ServeConfig(probe_repeats=3),
                       initial=initial)
    cfg = _loop_config({"serve_batch": [2, 4, 8], "cache_len": [64]}, initial)
    events = []
    with KermitSession(cfg, executor=ex) as session:
        session.subscribe(None, events.append)
        final = run_serving_session(session, ex)

    wl = ex.window_log
    assert len(wl) == traffic.n_windows
    change_w = traffic.phase_boundaries()[0]
    changes = [wl[i]["window"] for i in range(1, len(wl))
               if wl[i]["tunables"] != wl[i - 1]["tunables"]]
    replans = [w for w in changes if w >= change_w]
    kinds = {e.kind for e in events}
    assert replans, (changes, sorted(kinds))
    assert EventKind.DRIFT.value in kinds
    assert EventKind.RETUNE.value in kinds
    w0 = replans[0]
    p99_before = np.median([w["p99"] for w in wl
                            if change_w <= w["window"] < w0])
    p99_after = np.median([w["p99"] for w in wl if w["window"] >= w0])
    assert p99_after <= p99_before
    # the committed winner is what the executor is actually running
    assert final == ex.current
    assert final.serve_batch in (2, 4, 8)


def test_checkpoint_restore_with_serve_executor(tmp_path, engine):
    """KermitSession.checkpoint/restore round-trips the ServeExecutor's
    journaled state (cursor, counters, window log, calibration unit), and a
    restored stack finishes the trace where the original would."""
    def stack():
        traffic = TrafficGenerator.kway(("chat",), window_size=8, seed=5,
                                        n_windows=6, gap=1.0)
        return ServeExecutor(engine, traffic, initial=INITIAL)

    cfg = KermitConfig(monitor=MonitorConfig(window_size=8),
                       analysis=AnalysisConfig(interval=50, min_windows=6),
                       plan=PlanConfig(space={"serve_batch": [2, 4]}))
    exA = stack()
    sA = KermitSession(cfg, executor=exA)
    stream = exA.telemetry_stream()
    for _ in range(3):
        sA.step_batch(next(stream))
    snap = tmp_path / "serve.npz"
    sA.checkpoint(snap)
    sA.close()

    exB = stack()
    sB = KermitSession.restore(snap, executor=exB)
    assert exB._cursor == exA._cursor == 3
    assert exB.windows_served == 3
    assert exB._unit == exA._unit
    assert exB.current == exA.current
    assert [w["window"] for w in exB.window_log] == [0, 1, 2]
    assert exB.window_log == exA.window_log
    assert (exB.applied, exB.measured) == (exA.applied, exA.measured)
    sB.run_live(exB.telemetry_stream())
    sB.close()
    assert [w["window"] for w in exB.window_log] == list(range(6))
    assert exB._cursor == 6


def test_serve_config_round_trip_rejects_unknown():
    sc = ServeConfig(probe_repeats=3, tail_weight=0.25)
    assert ServeConfig.from_dict(sc.to_dict()) == sc
    with pytest.raises(ValueError, match="unknown ServeConfig"):
        ServeConfig.from_dict({"archs": "typo"})
