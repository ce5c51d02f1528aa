"""The program's own spans (``repro.runtime.spans``), as the benchmark reads
them: the log kept in memory for the whole run, and the profiler's copy on
the host plane of a traced window.

``Spans`` holds the in-memory log in seconds from the window's start, so
set-up spans start before 0.  ``load`` reads the ``kermit.*`` host events
of a trace; ``reduce`` puts device busy time against them and names each
idle gap by the innermost span of either kind (``bench.*`` or ``kermit.*``)
over its middle, leaving the gaps themselves as ``trace.reduce`` finds
them, and ``into`` puts that into the reduction the readers get.
``call_phases``, ``totals`` and ``span_check`` give the fact lines.

``run.py`` turns the program's spans on in a traced run only, before
set-up, so that a run without the trace records nothing and its end-to-end
numbers come from the path they always came from.
"""
from __future__ import annotations

import dataclasses
import glob
from dataclasses import dataclass

import numpy as np

from chipbench import trace as TR

PREFIX = "kermit."


@dataclass
class Span:
    id: int
    parent: int            # id of the span it began inside, or None
    name: str
    start: float           # s from the window's start
    end: float
    seconds: float         # from the clock's integer reads, exactly
    self_s: float          # less the spans inside it
    compiles: int
    compile_s: float
    gc_s: float
    attrs: dict


class Spans:
    """The program's span log, on the window's clock."""

    def __init__(self, log: dict, t0: float, seconds: float = np.inf):
        """``log``: ``SpanLog.to_json()``; ``t0``: the window's start, in
        seconds of ``time.perf_counter()``; ``seconds``: its length."""
        col = {c: i for i, c in enumerate(log["columns"])}
        ns0 = t0 * 1e9
        self.seconds = seconds
        self.dropped = log["dropped"]
        self.all = [Span(r[col["id"]], r[col["parent"]], r[col["name"]],
                         (r[col["start_ns"]] - ns0) / 1e9,
                         (r[col["end_ns"]] - ns0) / 1e9,
                         (r[col["end_ns"]] - r[col["start_ns"]]) / 1e9,
                         (r[col["end_ns"]] - r[col["start_ns"]]
                          - r[col["child_ns"]]) / 1e9,
                         r[col["compiles"]], r[col["compile_ns"]] / 1e9,
                         r[col["gc_ns"]] / 1e9, r[col["attrs"]] or {})
                    for r in log["spans"]]
        self.all.sort(key=lambda s: s.start)
        self._kids: dict = {}
        for sp in self.all:
            self._kids.setdefault(sp.parent, []).append(sp)

    def named(self, name: str, lo=-np.inf, hi=np.inf) -> list:
        """Spans of ``name`` that began in ``[lo, hi)``, in start order."""
        return [s for s in self.all if s.name == name and lo <= s.start < hi]

    def children(self, span: Span, name: str = None) -> list:
        """The spans that began directly inside ``span`` (of ``name``)."""
        return [s for s in self._kids.get(span.id, [])
                if name is None or s.name == name]

    def within(self, span: Span, name: str) -> list:
        """Spans of ``name`` at any depth inside ``span``."""
        out, todo = [], list(self.children(span))
        while todo:
            s = todo.pop()
            out += [s] if s.name == name else []
            todo += self.children(s)
        return out

    def totals(self, lo=-np.inf, hi=np.inf) -> dict:
        """Per name, over the spans that began in ``[lo, hi)``: seconds,
        count, self seconds, and the compiles, compile seconds and GC
        seconds charged to the spans themselves."""
        out: dict = {}
        for s in self.all:
            if lo <= s.start < hi:
                t = out.setdefault(s.name, dict.fromkeys(
                    ("seconds", "count", "self_s", "compiles", "compile_s",
                     "gc_s"), 0))
                for k, v in (("seconds", s.seconds), ("count", 1),
                             ("self_s", s.self_s), ("compiles", s.compiles),
                             ("compile_s", s.compile_s), ("gc_s", s.gc_s)):
                    t[k] += v
        return out

    def calls(self, lo=-np.inf, hi=np.inf) -> list:
        """The engine calls the client made (``kermit.serve`` at the top,
        not inside a probe) that began in ``[lo, hi)``."""
        return [s for s in self.named("kermit.serve", lo, hi)
                if s.parent is None]


def align(log: dict, call_starts) -> float:
    """The window's start on the program's clock, from the dispatch times
    (s from its start) of every call the client made in the window's
    segment, those after the close included: the last
    ``len(call_starts)`` top-level ``kermit.serve`` spans are those calls,
    each begun a few microseconds after its dispatch was stamped."""
    top = Spans(log, 0.0).calls()[-len(call_starts):]
    if len(top) != len(call_starts):
        raise ValueError("fewer engine-call spans than calls")
    return float(np.min([s.start - t for s, t in zip(top, call_starts)]))


def load(trace_dir: str) -> list:
    """``(name, start_ns, end_ns)`` of every ``kermit.*`` host event."""
    import jax
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines
            for e in line.events if e.name.startswith(PREFIX)]


@dataclass
class Reduced:
    program_span_s: dict          # kermit span name -> s (merged) in window
    busy_in_program_span_s: dict  # same -> device busy inside
    idle_gaps: list               # [[innermost span, s]] longest 10
    spans: list                   # (name, start_ns, end_ns) in the window


def reduce(ev: TR.Events, program: list) -> Reduced:
    lo, hi = TR.window(ev)
    chips = sorted({c for *_, c in ev.device}) or [0]
    busy0 = TR.union(TR.clip([(s, e) for _, s, e, c in ev.device
                              if c == chips[0]], lo, hi))
    inside = [p for p in program if lo <= p[1] < hi]
    span_s, busy_s = {}, {}
    for name in sorted({n for n, _, _ in inside}):
        u = TR.union(TR.clip([(s, e) for n, s, e in inside if n == name],
                             lo, hi))
        span_s[name] = TR.length(u) / 1e9
        busy_s[name] = TR.length(TR.intersect(busy0, u)) / 1e9
    named = [(n[len(TR.SPAN_PREFIX):], s, e) for n, s, e in ev.span
             if n != TR.WINDOW_SPAN] + inside
    gaps = []
    idle = sorted(TR.gaps(busy0, lo, hi), key=lambda g: g[0] - g[1])[:10]
    for s, e in idle:
        mid, best = (s + e) / 2, None
        for n, a, b in named:
            if a <= mid <= b and (best is None or b - a < best[1]):
                best = (n, b - a)
        gaps.append([best[0] if best else "none", (e - s) / 1e9])
    return Reduced(span_s, busy_s, gaps, inside)


def into(trace: TR.Reduced, program: Reduced) -> TR.Reduced:
    """The harness's reduction of a trace with the program's spans put in:
    each span name's seconds and the device's busy seconds inside it, and
    the idle gaps named by the innermost span of either kind."""
    return dataclasses.replace(
        trace, program_span_s=program.program_span_s,
        busy_in_program_span_s=program.busy_in_program_span_s,
        idle_gaps=program.idle_gaps)


PHASES = ["t_dispatch_s", "prefill_dispatch_ms", "grow_ms", "prefill_wait_ms",
          "decode_dispatch_ms", "decode_wait_ms", "collect_ms", "other_ms",
          "step_p99_ms", "steps"]


def call_phases(sp: Spans) -> dict:
    """Per engine call of the window, where its host time went: the
    prefill's dispatch, the cache grow, the wait for the logits, the decode
    steps' dispatch, the wait for the last token, the collect of the tokens,
    the rest of the call, and the 99th percentile of one step's dispatch."""
    rows = []
    for c in sp.calls(0.0, sp.seconds):
        pf, = sp.children(c, "kermit.prefill")
        dc, = sp.children(c, "kermit.decode")
        col, = sp.children(c, "kermit.collect")
        grow, = sp.children(pf, "kermit.cache_grow")
        wait, = sp.children(dc, "kermit.decode_wait")
        steps = [s.seconds for s in sp.children(dc, "kermit.decode_step")]
        rows.append([c.start] + [1e3 * x for x in (
            grow.start - pf.start, grow.seconds, pf.end - grow.end,
            wait.start - dc.start, wait.seconds, col.seconds,
            c.seconds - pf.seconds - dc.seconds - col.seconds,
            float(np.percentile(steps, 99)) if steps else 0.0)]
            + [len(steps)])
    return {"columns": PHASES, "rows": rows}


def span_check(sp: Spans, run: dict, reduced: Reduced = None) -> dict:
    """Each program span beside another reading of the same time: the
    engine's ``ServeReport`` times, the client's host clock around the same
    calls, and, traced, the profiler's copy of each span."""
    seconds = sp.seconds
    calls = [c for c in run["seg"].calls if c.t_dispatch < seconds]
    spans = sp.calls(0.0, seconds)
    out = {"report": {"calls": [len(spans), len(calls)]}}
    if len(spans) == len(calls):
        out["report"].update({
            k + "_max_abs_s": max((abs(sp.children(s, "kermit." + k)[0]
                                       .seconds - getattr(c, k + "_s"))
                                   for s, c in zip(spans, calls)), default=0.0)
            for k in ("prefill", "decode")})
    steps = [s for s in run["seg"].steps if s.t_start < seconds]
    out["host_clock"] = {
        "serve": {"program_s": sum(s.seconds for s in spans),
                  "host_s": sum(c.t_end - c.t_dispatch for c in calls)},
        "step_batch": {
            "program_s": sum(s.seconds for s in sp.named(
                "kermit.step_batch", 0.0, seconds)),
            "host_s": sum(s.wall_s for s in steps)}}
    if reduced is not None:
        lo, hi = run["traced"]
        trace = {}
        for name in sorted({n for n, _, _ in reduced.spans}):
            t = sorted((s, e) for n, s, e in reduced.spans if n == name)
            m = sp.named(name, lo, hi)
            d = {"trace_s": sum(e - s for s, e in t) / 1e9,
                 "memory_s": sum(s.seconds for s in m),
                 "count": [len(t), len(m)]}
            if len(t) == len(m):
                d["max_abs_s"] = max(abs((e - s) / 1e9 - x.seconds)
                                     for (s, e), x in zip(t, m))
            trace[name] = d
        out["trace"] = trace
    return out
