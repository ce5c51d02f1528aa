"""KermitSession facade: config-tree round-trip, event subscription, the
Execute phase, legacy-shim parity, window-count staleness, knowledge
persistence, and the Analyse phase off the serving thread."""
import json
import threading

import numpy as np
import pytest

from repro.configs.base import DEFAULT_TUNABLES, Tunables
from repro.core.analyser import KermitAnalyser
from repro.core.explorer import Explorer
from repro.core.monitor import KermitMonitor, WorkloadContext
from repro.core.plugin import KermitPlugin
from repro.core.simulator import generate
from repro.kermit import (AnalysisConfig, AutonomicEvent, CallableExecutor,
                          EventKind, ExecConfig, KermitConfig, KermitSession,
                          KnowledgeConfig, MonitorConfig, PlanConfig,
                          SimulatorExecutor, resolve_impl)
from repro.runtime import spans
from repro.scenarios.runner import _build_config, _build_stack, load_manifest

SPACE = {"microbatches": [1, 2, 4], "remat": ["dots", "none"]}


def _objective(t: Tunables) -> float:
    return abs(t.microbatches - 2) + (0.0 if t.remat == "none" else 0.5)


def _cfg(**kw):
    base = dict(monitor=MonitorConfig(window_size=8),
                analysis=AnalysisConfig(interval=10, dbscan_eps=0.35),
                plan=PlanConfig(space=SPACE))
    base.update(kw)
    return KermitConfig(**base)


# -- config tree ---------------------------------------------------------------


def test_config_round_trip_default():
    c = KermitConfig()
    assert KermitConfig.from_dict(c.to_dict()) == c


def test_config_round_trip_customized_through_json():
    c = KermitConfig(
        monitor=MonitorConfig(window_size=8, retention=128),
        analysis=AnalysisConfig(interval=5, dbscan_eps=0.2,
                                synthesize_hybrids=False),
        plan=PlanConfig(space=SPACE, max_staleness_windows=7,
                        default_tunables=DEFAULT_TUNABLES.replace(
                            microbatches=4).as_dict()),
        knowledge=KnowledgeConfig(root="/tmp/x", drift_eps=0.5),
        execute=ExecConfig(apply_on_retune=False, measure_repeats=3),
        impl="legacy", max_events=99)
    wire = json.dumps(c.to_dict())                 # a real JSON experiment spec
    assert KermitConfig.from_dict(json.loads(wire)) == c


def test_config_rejects_unknown_keys_and_impls():
    with pytest.raises(ValueError, match="unknown KermitConfig keys"):
        KermitConfig.from_dict({"montior": {}})
    with pytest.raises(ValueError, match="monitor.window_sz"):
        KermitConfig.from_dict({"monitor": {"window_sz": 4}})
    with pytest.raises(ValueError, match="impl"):
        KermitConfig(impl="turbo")


def test_impl_policy_resolution():
    assert resolve_impl("auto") == (True, True, "auto")
    assert resolve_impl("legacy") == (False, False, "legacy")
    fm, fa, impl = resolve_impl("pallas_interpret")
    assert (fm, fa, impl) == (True, True, "pallas_interpret")
    sess = KermitSession(KermitConfig(impl="legacy"))
    assert sess.monitor.fast is False and sess.analyser.fast is False
    assert sess.analyser.dbscan_impl == "legacy"


def test_explorer_rejects_space_typos():
    with pytest.raises(ValueError, match="microbatchez"):
        Explorer({"microbatchez": [1, 2]})


# -- event subscription --------------------------------------------------------


def test_subscribe_filters_replays_and_unsubscribes():
    sess = KermitSession(_cfg())
    for i in range(6):
        sess._record(AutonomicEvent(i, EventKind.TRANSITION.value, -1))
    sess._record(AutonomicEvent(6, EventKind.RETUNE.value, 0,
                                tunables=DEFAULT_TUNABLES.as_dict()))

    got_all, got_ret = [], []
    # replay catches late-attaching sinks up from the bounded deque
    sess.subscribe(None, got_all.append, replay=3)
    assert [e.window_id for e in got_all] == [4, 5, 6]
    off = sess.subscribe(EventKind.RETUNE, got_ret.append, replay=10)
    assert [e.window_id for e in got_ret] == [6]

    sess._record(AutonomicEvent(7, EventKind.RETUNE.value, 0))
    sess._record(AutonomicEvent(8, EventKind.TRANSITION.value, -1))
    assert [e.window_id for e in got_ret] == [6, 7]      # kind-filtered
    assert [e.window_id for e in got_all] == [4, 5, 6, 7, 8]

    off()
    off()                                                # idempotent
    sess._record(AutonomicEvent(9, EventKind.RETUNE.value, 0))
    assert [e.window_id for e in got_ret] == [6, 7]
    assert sess.events_total == 10


# -- the closed loop through an Executor ---------------------------------------


def test_simulator_executor_closes_the_loop():
    ex = SimulatorExecutor([("dense_train", 14), ("decode_serve", 14)],
                           window_size=8, seed=0)
    retunes = []
    with KermitSession(_cfg(), executor=ex) as sess:
        sess.subscribe(EventKind.RETUNE, retunes.append)
        tun = sess.run()                       # telemetry from the executor
    assert retunes, "plan phase should commit at least one retune"
    # the committed winner was applied to the executor (Execute phase)
    assert ex.current == tun
    assert (tun.microbatches, tun.remat) == (2, "none")  # sim cost optimum
    assert ex.applied >= len(retunes) and ex.measured > 0


def test_session_without_executor_fails_loudly_on_search():
    sim = generate([("dense_train", 14)], window_size=8, seed=3)
    sess = KermitSession(_cfg())
    with pytest.raises(RuntimeError, match="no Executor bound"):
        sess.step_batch(sim.samples)


def test_bind_executor_guard():
    sess = KermitSession(_cfg(), executor=CallableExecutor(_objective))
    with pytest.raises(RuntimeError, match="already has an executor"):
        sess.bind_executor(CallableExecutor(_objective))
    sess.bind_executor(CallableExecutor(_objective), replace=True)


# -- legacy shim parity (acceptance criterion) ---------------------------------


def _event_key(events):
    # "seconds" is wall time — everything else must be bit-equal
    return [(e.window_id, e.kind, e.label, e.tunables,
             {k: v for k, v in e.detail.items() if k != "seconds"})
            for e in events]


def test_manager_shim_warns_and_matches_session_events():
    sim = generate([("dense_train", 10), ("decode_serve", 10),
                    ("dense_train", 6)], window_size=8, seed=15)

    with pytest.warns(DeprecationWarning, match="AutonomicManager"):
        from repro.core.autonomic import AutonomicManager
        mgr = AutonomicManager(window_size=8, analysis_interval=10,
                               dbscan_eps=0.35, explorer=Explorer(SPACE))
    with mgr:
        for s in sim.samples:
            mgr.step(s, _objective)

    sess = KermitSession(_cfg(), executor=CallableExecutor(_objective))
    with sess:
        sess.step_batch(sim.samples)

    assert _event_key(mgr.events) == _event_key(sess.events)
    assert any(e.kind == "retune" for e in sess.events)
    assert mgr.current == sess.current
    assert mgr.events_total == sess.events_total
    assert mgr.summary()["windows"] == sess.summary()["windows"]


def test_plugin_max_staleness_s_deprecated(tmp_path):
    from repro.core.knowledge import WorkloadDB
    with pytest.warns(DeprecationWarning, match="max_staleness_s"):
        KermitPlugin(WorkloadDB(tmp_path), KermitMonitor(window_size=4),
                     max_staleness_s=300.0)


# -- window-count staleness (deterministic, satellite 1) -----------------------


def test_staleness_is_window_count_based_and_deterministic(tmp_path):
    from repro.core.knowledge import WorkloadDB
    db = WorkloadDB(tmp_path)
    label = db.insert({"mean": np.zeros(4), "std": np.ones(4), "n": 16})
    db.set_config(label, DEFAULT_TUNABLES.as_dict(), optimal=True)
    mon = KermitMonitor(window_size=4)

    class FakeClf:
        def predict(self, x):
            return np.array([label])
    mon.classifier = FakeClf()
    mon.ingest_array(generate([("dense_train", 2)], window_size=4,
                              seed=4).samples)

    # injected window-count clock far ahead -> pulled context is stale
    plug = KermitPlugin(db, mon, Explorer(SPACE), max_staleness_windows=8,
                        clock=lambda: mon.windows_emitted + 100)
    assert plug.on_resource_request(_objective) == plug.default
    assert plug.stats.stale_contexts == 1

    # same request against the monitor's own counter: fresh, reuses optimum
    plug2 = KermitPlugin(db, mon, Explorer(SPACE), max_staleness_windows=8)
    assert plug2.on_resource_request(_objective) == DEFAULT_TUNABLES
    assert plug2.stats.stale_contexts == 0 and plug2.stats.reused == 1

    # pinned contexts never trip the guard, however old
    old = WorkloadContext(window_id=0, timestamp=0.0, current_label=label,
                          predicted={}, in_transition=False)
    plug3 = KermitPlugin(db, mon, Explorer(SPACE), max_staleness_windows=0,
                         clock=lambda: 10_000)
    assert plug3.on_resource_request(_objective, ctx=old) == DEFAULT_TUNABLES
    assert plug3.stats.stale_contexts == 0


# -- knowledge save/load round-trip (satellite 2) ------------------------------


def test_workloaddb_explicit_save_load_round_trip(tmp_path):
    from repro.core.knowledge import WorkloadDB
    db = WorkloadDB()                                   # root-less, in-memory
    a = db.insert({"mean": np.ones(3, np.float32), "std": np.ones(3), "n": 8})
    h = db.insert({"mean": np.zeros(3, np.float32), "std": np.ones(3), "n": 4},
                  is_synthetic=True, pair=(a, 7))
    db.set_config(a, DEFAULT_TUNABLES.replace(microbatches=4).as_dict(),
                  optimal=True)
    path = tmp_path / "snap.json"
    db.save(path)

    db2 = WorkloadDB()
    assert db2.load(path) is True
    assert db2.labels() == db.labels()
    assert db2.get(a).config == db.get(a).config
    # pair provenance survives JSON as a tuple, not a list
    assert db2.get(h).pair == (a, 7) and isinstance(db2.get(h).pair, tuple)
    assert db2.new_label() == max(db.labels()) + 1      # counter restored
    assert db2.load(tmp_path / "missing.json") is False


def test_session_save_knowledge_explicit_path(tmp_path):
    ex = SimulatorExecutor([("dense_train", 14)], window_size=8, seed=0)
    with KermitSession(_cfg(), executor=ex) as sess:
        sess.run()
        path = tmp_path / "kb.json"
        sess.save_knowledge(path)
    from repro.core.knowledge import WorkloadDB
    db = WorkloadDB()
    assert db.load(path)
    assert len(db.records) == len(sess.db.records)


# -- Analyse off the serving thread --------------------------------------------

SESSION_SCENARIOS = sorted(
    name for name, spec in load_manifest()["scenarios"].items()
    if spec.get("kind", "session") == "session")


class _InlineSession(KermitSession):
    """Joins each analysis before the window's step returns: the loop as
    it ran with Analyse on the serving thread."""

    def _on_context(self, ctx):
        super()._on_context(ctx)
        self.sync()
        return self.current


def _window_by_window(session, samples):
    W = session.monitor.window_size
    for i in range(0, len(samples), W):
        session.step_batch(samples[i:i + W])
    session.sync()
    return [(e.window_id, e.kind, e.label, e.tunables,
             {k: v for k, v in e.detail.items() if k != "seconds"})
            for e in session.events]


@pytest.mark.parametrize("scenario", SESSION_SCENARIOS)
def test_background_analyse_decides_as_inline(scenario):
    """Fed one window per ``step_batch`` call, as a server feeds it, the
    loop with Analyse on the worker emits the same events (ANALYSIS and
    RETUNE among them), labels and committed tunables, window for window,
    as the same code joining every analysis at once."""
    spec = load_manifest()["scenarios"][scenario]
    runs = []
    for cls in (KermitSession, _InlineSession):
        ex, chaos = _build_stack(spec, seed=0)
        with cls(_build_config(spec, "auto"), executor=ex) as session:
            events = _window_by_window(session, chaos.samples)
            runs.append((events, session.current, session.summary()))
    (bg, bg_final, bg_sum), (inline, inline_final, _) = runs
    kinds = [e[1] for e in bg]
    assert EventKind.ANALYSIS.value in kinds
    assert EventKind.RETUNE.value in kinds
    assert bg == inline
    assert bg_final == inline_final
    assert bg_sum["background_analyses"] == kinds.count(
        EventKind.ANALYSIS.value)


@pytest.fixture
def held_analysis(monkeypatch):
    """``KermitAnalyser.run`` held, once it has run, until the returned
    ``release`` is set (or 30 s pass, so a wrong join fails the test rather
    than hanging it); ``ran`` is set when the hold begins."""
    ran, release = threading.Event(), threading.Event()
    run = KermitAnalyser.run

    def held(self, ws, **kw):
        rep = run(self, ws, **kw)
        ran.set()
        release.wait(30)
        return rep

    monkeypatch.setattr(KermitAnalyser, "run", held)
    yield ran, release
    release.set()


def _held_session(windows: int):
    """A session fed ``windows`` windows, one per call, whose last window
    handed its analysis to the worker; returns (session, samples)."""
    ex = SimulatorExecutor([("dense_train", 12)], window_size=8, seed=0)
    sess = KermitSession(
        _cfg(analysis=AnalysisConfig(interval=windows, min_windows=4)),
        executor=ex)
    for w in range(windows):
        sess.step_batch(ex.samples[8 * w:8 * (w + 1)])
    return sess, ex.samples


def _analyses(sess):
    return [e for e in sess.events if e.kind == EventKind.ANALYSIS.value]


def test_step_batch_returns_before_a_slow_analysis(held_analysis):
    ran, release = held_analysis
    sess, samples = _held_session(4)
    pending, ctx = sess._pending
    assert ran.wait(30) and not pending.future.done()
    # the analysis wrote its knowledge into a fork: the session's store,
    # models and events are untouched until the join
    assert len(pending.job.db.records) >= 1
    assert len(sess.db.records) == 0 and sess.analyser.classifier is None
    assert not _analyses(sess)
    threading.Timer(0.2, release.set).start()
    sess.step_batch(samples[32:40])           # joins before ingesting
    assert sess._pending is None and pending.future.done()
    analysis, = _analyses(sess)
    assert analysis.window_id == ctx.window_id
    assert len(sess.db.records) >= 1
    assert sess.monitor.classifier is sess.analyser.classifier is not None
    s = sess.summary()
    assert s["background_analyses"] == 1
    assert s["analysis_wait_s"] > 0.05


@pytest.mark.parametrize("reader", ["summary", "save_knowledge",
                                    "checkpoint", "invalidate"])
def test_readers_of_the_loop_state_join_first(held_analysis, reader,
                                              tmp_path):
    sess, _ = _held_session(4)
    assert sess._pending is not None
    held_analysis[1].set()
    path = tmp_path / "state"
    if reader == "summary":
        assert sess.summary()["known_workloads"] >= 1
    elif reader == "save_knowledge":
        sess.save_knowledge(path)
        assert json.loads(path.read_text())["records"]
    elif reader == "checkpoint":
        sess.checkpoint(path)
        restored = KermitSession.restore(path)
        assert len(restored.db.records) == len(sess.db.records) >= 1
    else:
        sess.invalidate()
    assert sess._pending is None and len(_analyses(sess)) == 1


def test_a_window_that_plans_joins_its_analysis_first():
    """When the analysis window's label differs from the planned one, the
    plan reads the knowledge the analysis writes, so that window's call
    joins the analysis and plans before it returns."""
    ex = SimulatorExecutor([("dense_train", 12)], window_size=8, seed=0)
    sess = KermitSession(_cfg(analysis=AnalysisConfig(interval=4,
                                                      min_windows=4)),
                         executor=ex)
    for w in range(3):
        sess.step_batch(ex.samples[8 * w:8 * (w + 1)])
    sess.invalidate()                 # the 4th window's label is now new
    plan = sess.plugin.on_resource_request
    seen = []

    def checked(objective, ctx=None):
        seen.append((ctx.window_id, sess._pending is None,
                     len(_analyses(sess))))
        return plan(objective, ctx=ctx)

    sess.plugin.on_resource_request = checked
    sess.step_batch(ex.samples[24:32])
    assert sess._pending is None
    analysis, = _analyses(sess)
    assert seen == [(analysis.window_id, True, 1)]


def test_the_join_is_a_span_and_two_counters():
    ex = SimulatorExecutor([("dense_train", 8), ("moe_train", 8)],
                           window_size=8, seed=0)
    sess = KermitSession(_cfg(analysis=AnalysisConfig(interval=4,
                                                      min_windows=4)),
                         executor=ex)
    log = spans.enable()
    try:
        _window_by_window(sess, ex.samples)
    finally:
        spans.disable()
    s = sess.summary()
    names = [r[2] for r in log.records]
    assert s["background_analyses"] == len(_analyses(sess)) >= 2
    assert names.count("kermit.analyse_join") == s["background_analyses"]
    assert names.count("kermit.analyse") == s["background_analyses"]
    joins = [r for r in log.records if r[2] == "kermit.analyse_join"]
    assert s["analysis_wait_s"] <= sum(r[4] - r[3] for r in joins) / 1e9
    # the worker's spans are its own thread's: never inside the loop's
    by_id = {r[0]: r for r in log.records}
    for r in log.records:
        if r[2] == "kermit.analyse":
            assert r[1] is None
        if r[2].startswith("kermit.analyse."):
            assert by_id[r[1]][2] == "kermit.analyse"
