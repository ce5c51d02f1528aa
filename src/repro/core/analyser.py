"""KWanl — the off-line (batch) analysis subsystem.

Implements paper Algorithm 2 + the automated training pipeline (§7):
  1. ChangeDetector.batch flags transition windows
  2. transitions are filtered out; DBSCAN discovers workload clusters
  3. clusters are characterized and matched against WorkloadDB (Welch);
     matches update characterizations + drift flags, novelties get fresh
     integer labels — labelling needs no human
  4. training sets are generated: windows->labels (WorkloadClassifier),
     rate-of-change transition windows (TransitionClassifier), synthesized
     hybrids (ZSL), label sequences (WorkloadPredictor)
  5. classifiers are (re)trained

``KermitAnalyser.start`` runs the same analysis on the process's one Analyse
worker thread (``ANALYSIS_WORKER``) against a private fork of the knowledge
base; ``PendingAnalysis.join`` installs its models and knowledge.
"""
from __future__ import annotations

import atexit
import copy
import queue
import threading
from concurrent.futures import Future, wait
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from repro.core.change_detector import ChangeDetector
from repro.core.characterize import characterize
from repro.core.dbscan import dbscan
from repro.core.forest import ForestConfig, RandomForest
from repro.core.knowledge import WorkloadDB
from repro.core.lstm import HORIZONS, PredictorConfig, WorkloadPredictor
from repro.core.synthesizer import sample_pure, synthesize
from repro.core.windows import WindowSeries, rate_of_change
from repro.runtime import spans


# fast-path training bounds: bootstrap draws per tree, predictor training
# subsample / batch / width (see ROADMAP "analysis-path latency budget")
_FAST_MAX_SAMPLES = 768
_FAST_PREDICTOR_SAMPLES = 768
_FAST_PREDICTOR_BATCH = 256
_FAST_PREDICTOR_HIDDEN = 32


@dataclass
class AnalysisReport:
    n_windows: int = 0
    n_transition_windows: int = 0
    clusters: int = 0
    new_labels: list = field(default_factory=list)
    matched_labels: list = field(default_factory=list)
    drifted_labels: list = field(default_factory=list)
    window_labels: Optional[np.ndarray] = None   # per-window DB label (-1 noise)
    discover_seconds: float = 0.0                # A-phase latency accounting
    train_seconds: float = 0.0

    @property
    def analysis_seconds(self) -> float:
        return self.discover_seconds + self.train_seconds


class AnalysisWorker:
    """The process's one Analyse thread: runs the jobs handed to it one at a
    time, in the order they came, so every session of a process shares one
    thread.  It is a daemon thread, started at the first job, so the
    process can exit with it running; at exit the process waits a bounded
    time for the jobs already handed in (``drain``), since a thread still
    inside a compiled call when the interpreter finalizes aborts it."""

    def __init__(self):
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def submit(self, fn, *args, **kw) -> Future:
        fut: Future = Future()
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._serve, name="kermit-analyse", daemon=True)
                self._thread.start()
        self._jobs.put((fut, fn, args, kw))
        return fut

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait until every job handed in so far has finished; False if
        ``timeout`` seconds passed first."""
        if self._thread is None or not self._thread.is_alive():
            return True
        return not wait([self.submit(lambda: None)], timeout).not_done

    def _serve(self) -> None:
        while True:
            fut, fn, args, kw = self._jobs.get()
            try:
                fut.set_result(fn(*args, **kw))
            except BaseException as e:       # handed to the joining thread
                fut.set_exception(e)


ANALYSIS_WORKER = AnalysisWorker()
atexit.register(ANALYSIS_WORKER.drain, 120.0)


class PendingAnalysis:
    """An analysis running on ``ANALYSIS_WORKER``; ``join`` waits for it
    and installs what it produced."""

    def __init__(self, analyser: "KermitAnalyser", job: "KermitAnalyser",
                 future: Future):
        self.analyser, self.job, self.future = analyser, job, future

    def join(self) -> "AnalysisReport":
        """Wait for the analysis, then install its classifiers, predictor
        and knowledge into the analyser and its WorkloadDB; re-raises what
        the analysis raised."""
        rep = self.future.result()
        a, job = self.analyser, self.job
        a.classifier = job.classifier
        a.transition_classifier = job.transition_classifier
        a.predictor = job.predictor
        a.db.adopt(job.db)
        return rep


class KermitAnalyser:
    """``fast=True`` (default) runs the compiled analysis path: streaming
    DBSCAN (kernels/dispatch picks compiled Pallas or XLA tiles), jit-cached
    forest training and the single-scan predictor train loop.  ``fast=False``
    reproduces the seed implementation end to end — interpret-mode dense
    distance matrix, one-hop label propagation and per-batch Python training
    — and exists for benchmarking (bench_analysis_latency) and parity tests.
    """

    def __init__(self, db: WorkloadDB, *,
                 detector: Optional[ChangeDetector] = None,
                 dbscan_eps: float = 0.35, dbscan_min_pts: int = 4,
                 max_classes: int = 64,
                 dbscan_impl: str = "auto", fast: bool = True):
        self.db = db
        self.detector = detector or ChangeDetector()
        self.eps = dbscan_eps
        self.min_pts = dbscan_min_pts
        self.max_classes = max_classes
        self.fast = fast
        self.dbscan_impl = dbscan_impl if fast else "legacy"
        self.classifier: Optional[RandomForest] = None
        self.transition_classifier: Optional[RandomForest] = None
        self.predictor: Optional[WorkloadPredictor] = None

    # -- Algorithm 2 ----------------------------------------------------------

    def discover(self, ws: WindowSeries) -> AnalysisReport:
        with spans.timed("kermit.analyse.discover") as t:
            rep = self._discover(ws)
        rep.discover_seconds = t.seconds
        return rep

    def _discover(self, ws: WindowSeries) -> AnalysisReport:
        rep = AnalysisReport(n_windows=len(ws))
        trans = self.detector.batch(ws)
        rep.n_transition_windows = int(trans.sum())
        steady_idx = np.where(~trans)[0]
        if steady_idx.size == 0:
            return rep
        X = ws.mean[steady_idx]
        labels = dbscan(X, self.eps, self.min_pts, impl=self.dbscan_impl)
        rep.clusters = int(labels.max() + 1) if labels.size else 0

        window_labels = np.full(len(ws), -1, np.int64)
        for c in range(rep.clusters):
            members = steady_idx[labels == c]
            char = characterize(ws.mean[members])
            match = self.db.find_match(char)
            if match is not None:
                drift = self.db.observe(match, char)
                rep.matched_labels.append(match)
                if drift:
                    rep.drifted_labels.append(match)
                window_labels[members] = match
            else:
                new = self.db.insert(char)
                rep.new_labels.append(new)
                window_labels[members] = new
        rep.window_labels = window_labels
        # convergence/bound maintenance: classes whose characterizations have
        # converged merge (newer label aliased onto older), over-bound stores
        # evict.  Remap freshly-labelled windows by membership — aliases
        # resolve to the survivor, labels the DB no longer holds (evicted by
        # this pass OR by an insert earlier in the loop) drop to noise — so
        # the training set never references a label the DB cannot resolve.
        self.db.consolidate()
        for u in np.unique(window_labels):
            if u < 0:
                continue
            r = self.db.resolve(int(u))
            if r not in self.db.records:
                r = -1
            if r != u:
                window_labels[window_labels == u] = r
        self.db.save()
        return rep

    # -- training pipeline (§7.2 steps 1-9) ------------------------------------

    def train(self, ws: WindowSeries, rep: AnalysisReport, *,
              synthesize_hybrids: bool = True, zsl_k: int = 2, seed: int = 0,
              predictor_cfg: Optional[PredictorConfig] = None,
              forest_cfg: Optional[ForestConfig] = None):
        wl = rep.window_labels
        if wl is None or (wl >= 0).sum() == 0:
            return self
        with spans.timed("kermit.analyse.train") as t:
            self._train(ws, wl, synthesize_hybrids, zsl_k, seed,
                        predictor_cfg, forest_cfg)
        rep.train_seconds = t.seconds
        return self

    def _train(self, ws, wl, synthesize_hybrids, zsl_k, seed, predictor_cfg,
               forest_cfg) -> None:
        mask = wl >= 0
        X = ws.mean[mask]
        y = wl[mask]

        # step 7: ZSL synthesis from pure characterizations (k-way mixtures
        # up to ``zsl_k`` concurrent archetypes).  One synthetic WorkloadDB
        # record per combination, ever: combos the knowledge base already
        # anticipates reuse their stored label (prototype refreshed) instead
        # of inserting a duplicate on every analysis run.
        if synthesize_hybrids:
            pure = self.db.pure_characterizations()
            Xs, ys, hybrids = synthesize(
                pure, n_per_class=100, seed=seed,
                next_label=self.db._next_label, k=zsl_k)
            for h in hybrids:
                existing = self.db.find_synthetic(h.pair)
                if existing is not None and existing != h.label:
                    self.db.refresh_synthetic(existing, h.prototype)
                    ys[ys == h.label] = existing
                elif len(self.db.records) < self.db.max_records:
                    self.db.insert(h.prototype, is_synthetic=True,
                                   pair=h.pair, label=h.label)
                # a full store skips the remaining anticipations rather
                # than churning labels through eviction every run; their
                # training rows are dropped by the membership filter below
            Xb, yb = sample_pure(pure, n_per_class=100, seed=seed + 1)
            if Xs.size:
                # a full store may have evicted an earlier hybrid while
                # inserting a later one; never train on unresolvable labels
                present = np.isin(ys, np.asarray(self.db.labels()))
                X = np.concatenate([X, Xb, Xs[present]])
                y = np.concatenate([y, yb, ys[present]])

        n_classes = int(max(self.db.labels(), default=0)) + 1
        max_samples = _FAST_MAX_SAMPLES if self.fast else 0
        fc = forest_cfg or ForestConfig(n_trees=24, depth=6,
                                        n_classes=min(n_classes,
                                                      self.max_classes),
                                        max_samples=max_samples)
        self.classifier = RandomForest(fc).fit(X, y, seed=seed,
                                               compiled=self.fast)

        # transition classifier on rate-of-change features
        roc = rate_of_change(ws.mean)
        ty = (wl < 0).astype(np.int64)       # 1 = transition/noise window
        tfc = ForestConfig(n_trees=16, depth=5, n_classes=2,
                           max_samples=max_samples)
        self.transition_classifier = RandomForest(tfc).fit(
            roc, ty, seed=seed, compiled=self.fast)

        # predictor on the label sequence (steady windows carry labels;
        # transitions inherit the previous label for sequence continuity) —
        # forward-fill vectorized via a running max of labelled indices
        idx = np.where(wl >= 0, np.arange(len(wl)), -1)
        np.maximum.accumulate(idx, out=idx)
        first = wl[wl >= 0]
        seq = np.where(idx >= 0, wl[np.maximum(idx, 0)],
                       first[0] if first.size else 0)
        if predictor_cfg is not None:
            pc = predictor_cfg
        elif self.fast:
            # bounded retraining: a uniform subsample of history windows
            # caps per-analysis compute regardless of N, and a larger batch
            # + loss-plateau early stopping keeps the compiled train loop
            # to a handful of epochs
            n_samples = min(len(seq) - PredictorConfig.window - max(HORIZONS),
                            _FAST_PREDICTOR_SAMPLES)
            pc = PredictorConfig(
                n_classes=max(int(seq.max()) + 1, 2), epochs=30,
                hidden=_FAST_PREDICTOR_HIDDEN, lr=1e-2,
                batch=max(16, min(_FAST_PREDICTOR_BATCH, n_samples)),
                early_stop_tol=1e-2, patience=2, target_loss=0.15,
                max_train_samples=_FAST_PREDICTOR_SAMPLES)
        else:
            pc = PredictorConfig(n_classes=max(int(seq.max()) + 1, 2),
                                 epochs=30)
        try:
            self.predictor = WorkloadPredictor(pc).fit(seq, seed=seed,
                                                       compiled=self.fast)
        except ValueError:
            self.predictor = None            # sequence too short
        self.db.save()
        # sync before the artifacts are handed to the monitor, so the
        # reported latency is honest (JAX dispatch is asynchronous)
        jax.block_until_ready([
            None if self.classifier is None else self.classifier.params,
            None if self.transition_classifier is None
            else self.transition_classifier.params,
            None if self.predictor is None else self.predictor.params])

    def run(self, ws: WindowSeries, **kw) -> AnalysisReport:
        with spans.span("kermit.analyse", windows=len(ws)) as sp:
            rep = self.discover(ws)
            self.train(ws, rep, **kw)
            sp.note(clusters=rep.clusters)
        return rep

    def start(self, ws: WindowSeries, **kw) -> PendingAnalysis:
        """``run(ws, **kw)`` on the Analyse worker.  The job runs on a copy
        of this analyser over a ``fork`` of its WorkloadDB, so nothing this
        analyser or its store hold changes until ``PendingAnalysis.join``.
        ``ws`` must not change meanwhile (``window_series(copy=True)``)."""
        job = copy.copy(self)
        job.db = self.db.fork()
        return PendingAnalysis(self, job,
                               ANALYSIS_WORKER.submit(job.run, ws, **kw))
