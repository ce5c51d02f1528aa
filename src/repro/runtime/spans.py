"""Named host spans, and the compiles and GC pauses charged to them.

The program's one timing path.  ``span(name, **attrs)`` marks a region of
host work; ``timed(name, **attrs)`` marks one and hands back its
``seconds``, so a layer that reports a time (``ServeReport.prefill_s``,
``MeasureCounters.measure_seconds``) reports the span's own clock reads.

Recording is off until ``enable()``.  Off, a ``span`` site costs one flag
test and ``timed`` two clock reads.  On, each span keeps its id, its
parent (the innermost span open when it began), its name, its start and
end from ``time.perf_counter_ns()`` and its attributes, and enters
``jax.profiler.TraceAnnotation`` under the same name and attributes: under
a profiler session it lies on the host plane, on the clock of the device's
programs.  Backend compiles (``jax.monitoring``) and Python GC pauses
(``gc.callbacks``) are charged to the innermost open span, or to
``SpanLog.outside`` when none is open.  Spans nest on one thread.
"""
from __future__ import annotations

import gc
import time

COLUMNS = ("id", "parent", "name", "start_ns", "end_ns", "child_ns",
           "compiles", "compile_ns", "gc_ns", "attrs")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_log = None               # the active SpanLog; None while recording is off
_annotation = None        # jax.profiler.TraceAnnotation, bound at enable()


class SpanLog:
    """Closed spans in the order they closed, at most ``capacity`` of them;
    ``dropped`` counts the spans that did not fit."""

    def __init__(self, capacity: int = 1 << 20):
        self.capacity = int(capacity)
        self.records: list = []       # one tuple per span, in COLUMNS order
        self.dropped = 0
        self.outside = {"compiles": 0, "compile_ns": 0, "gc_ns": 0}
        self._stack: list = []
        self._next_id = 0
        self._gc_t0 = None

    def _charge(self, key: str, ns: int) -> None:
        into = self._stack[-1].counts if self._stack else self.outside
        into[key] += ns
        if key == "compile_ns":
            into["compiles"] += 1

    def totals(self, lo: float = None, hi: float = None) -> dict:
        """Per span name, over the spans that began in ``[lo, hi)`` (seconds
        of ``time.perf_counter()``; an open end takes every span): seconds,
        count, self seconds (less the spans inside), and the compiles,
        compile seconds and GC seconds charged to the spans themselves."""
        lo = -float("inf") if lo is None else lo * 1e9
        hi = float("inf") if hi is None else hi * 1e9
        out: dict = {}
        for _, _, name, t0, t1, child, n, c_ns, g_ns, _ in self.records:
            if lo <= t0 < hi:
                t = out.setdefault(name, dict.fromkeys(
                    ("seconds", "count", "self_s", "compiles", "compile_s",
                     "gc_s"), 0))
                t["seconds"] += (t1 - t0) / 1e9
                t["count"] += 1
                t["self_s"] += (t1 - t0 - child) / 1e9
                t["compiles"] += n
                t["compile_s"] += c_ns / 1e9
                t["gc_s"] += g_ns / 1e9
        return out

    def to_json(self) -> dict:
        return {"columns": list(COLUMNS),
                "spans": [list(r) for r in self.records],
                "dropped": self.dropped, "outside": dict(self.outside)}


class _Null:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass

    def note(self, **attrs):
        """Attributes known only inside the block: the log keeps them; the
        profiler's copy has those given at the start."""


_NULL = _Null()


class _Timer(_Null):
    """Times its block with two clock reads and records nothing."""
    __slots__ = ("t0", "t1")

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter_ns()

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9


class _Span(_Timer):
    __slots__ = ("log", "name", "attrs", "id", "parent", "counts", "_ann")

    def __init__(self, log: SpanLog, name: str, attrs: dict):
        self.log, self.name, self.attrs = log, name, attrs

    def note(self, **attrs):
        self.attrs.update(attrs)

    def __enter__(self):
        log = self.log
        self.parent = log._stack[-1].id if log._stack else None
        self.id = log._next_id
        log._next_id += 1
        self.counts = {"child_ns": 0, "compiles": 0, "compile_ns": 0,
                       "gc_ns": 0}
        self._ann = _annotation(self.name, **self.attrs)
        self._ann.__enter__()
        log._stack.append(self)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter_ns()
        log, c, dt = self.log, self.counts, self.t1 - self.t0
        log._stack.pop()
        self._ann.__exit__(*exc)
        if log._stack:
            log._stack[-1].counts["child_ns"] += dt
        if len(log.records) < log.capacity:
            log.records.append((
                self.id, self.parent, self.name, self.t0, self.t1,
                c["child_ns"], c["compiles"], c["compile_ns"], c["gc_ns"],
                self.attrs or None))
        else:
            log.dropped += 1


def span(name: str, **attrs):
    """A span over the block, recorded while recording is on."""
    return _NULL if _log is None else _Span(_log, name, attrs)


def timed(name: str, **attrs):
    """As ``span``, but always timed: ``with timed(n) as t: ...``, then
    ``t.seconds``."""
    return _Timer() if _log is None else _Span(_log, name, attrs)


def _on_duration(event, secs, **_):
    if _log is not None and event == _COMPILE_EVENT:
        _log._charge("compile_ns", int(secs * 1e9))


def _on_gc(phase, info):
    log = _log
    if log is not None and phase == "start":
        log._gc_t0 = time.perf_counter_ns()
    elif log is not None and log._gc_t0 is not None:
        log._charge("gc_ns", time.perf_counter_ns() - log._gc_t0)
        log._gc_t0 = None


def enable(capacity: int = 1 << 20) -> SpanLog:
    """Start recording into a new log, and return it."""
    global _log, _annotation
    if _annotation is None:
        import jax.monitoring
        import jax.profiler
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        gc.callbacks.append(_on_gc)
        _annotation = jax.profiler.TraceAnnotation
    _log = SpanLog(capacity)
    return _log


def disable():
    """Stop recording; return the log that was being written, or None."""
    global _log
    log, _log = _log, None
    return log
