"""Reduction of a profiler trace to device busy time, program time and
idle gaps.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps three kinds of event, on one clock, in nanoseconds:

  device   programs run on a TPU ("XLA Modules" line of a ``/device:TPU:n``
           plane), named without the hash suffix: ``jit_serve_step``
  op       operations inside them ("XLA Ops" line), summed by name over
           the traced window, for the breakdown
  span     host annotations of the benchmark's own calls into each layer
           (``jax.profiler.TraceAnnotation``), named ``bench.*``

``reduce`` then works only on those lists, so a small recorded trace in
JSON tests it without a chip.
"""
from __future__ import annotations

import bisect
import glob
import re
from dataclasses import dataclass, field

_HASH = re.compile(r"\(\d+\)$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
SERVE_SPAN = "bench.serve"


@dataclass
class Events:
    device: list = field(default_factory=list)   # (name, start, end, chip)
    span: list = field(default_factory=list)     # (name, start, end)
    op_s: dict = field(default_factory=dict)     # op name -> s in window

    def to_json(self) -> dict:
        return {"device": self.device, "span": self.span, "op_s": self.op_s}

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls([tuple(x) for x in d["device"]],
                   [tuple(x) for x in d["span"]], dict(d["op_s"]))


def load_events(trace_dir: str) -> Events:
    import jax
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    # the profile hands out its planes once: keep them for both passes
    planes = list(jax.profiler.ProfileData.from_file(paths[0]).planes)
    ev = Events()
    for plane in planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                ev.span.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events
                               if e.name.startswith(SPAN_PREFIX))
    lo, hi = window(ev)
    for plane in planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                ev.device.extend(
                    (_HASH.sub("", e.name), e.start_ns,
                     e.start_ns + e.duration_ns, int(m.group(1)))
                    for e in line.events)
            elif line.name == "XLA Ops":
                # operations are many; keep only their seconds in the window
                for e in line.events:
                    s, t = max(e.start_ns, lo), min(e.start_ns
                                                    + e.duration_ns, hi)
                    if t > s:
                        name = e.name.split(" = ", 1)[0].lstrip("%")
                        ev.op_s[name] = ev.op_s.get(name, 0.0) + (t - s) / 1e9
    return ev


def window(ev: Events) -> tuple:
    wins = [(s, e) for n, s, e in ev.span if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, got "
                           f"{len(wins)}")
    return wins[0]


def union(intervals) -> list:
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def intersect(a, b) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, lo, hi) -> list:
    """Idle intervals of ``[lo, hi]`` outside the merged ``busy`` list."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _innermost(spans, t):
    """Name of the shortest span that holds time ``t``; "none" if none."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name[len(SPAN_PREFIX):], e - s)
    return best[0] if best else "none"


@dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over the chips used
    program_s: dict                   # device program name -> seconds
    program_in_serve_s: dict          # same, for programs run inside a
                                      # bench.serve span only
    span_s: dict                      # host span name -> seconds (merged)
    busy_in_span_s: dict              # host span name -> device busy inside
    device_ops: list                  # [[name, seconds]] top 10
    idle_gaps: list                   # [[host span, seconds]] longest 10
    # the program's own spans (``program_spans.into``), by name: seconds
    # (merged) in the window, and device busy inside them
    program_span_s: dict = field(default_factory=dict)
    busy_in_program_span_s: dict = field(default_factory=dict)


def reduce(ev: Events) -> Reduced:
    lo, hi = window(ev)
    chips = sorted({c for *_, c in ev.device}) or [0]
    busy_by_chip = {c: union(clip([(s, e) for _, s, e, cc in ev.device
                                   if cc == c], lo, hi)) for c in chips}
    program_s: dict = {}
    for name, s, e, _ in ev.device:
        for cs, ce in clip([(s, e)], lo, hi):
            program_s[name] = program_s.get(name, 0.0) + (ce - cs) / 1e9
    spans = [x for x in ev.span if x[0] != WINDOW_SPAN]
    serve = union([(s, e) for n, s, e in spans if n == SERVE_SPAN])
    starts = [s for s, _ in serve]
    program_in_serve_s: dict = {}
    for name, s, e, _ in ev.device:
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if lo <= mid <= hi and i >= 0 and mid <= serve[i][1]:
            program_in_serve_s[name] = program_in_serve_s.get(name, 0.0) \
                + (e - s) / 1e9
    span_s, busy_in_span_s = {}, {}
    busy0 = busy_by_chip[chips[0]]
    for name in sorted({n for n, _, _ in spans}):
        u = union(clip([(s, e) for n, s, e in spans if n == name], lo, hi))
        key = name[len(SPAN_PREFIX):]
        span_s[key] = length(u) / 1e9
        busy_in_span_s[key] = length(intersect(busy0, u)) / 1e9
    idle = sorted(gaps(busy0, lo, hi), key=lambda g: g[0] - g[1])[:10]
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(length(b) for b in busy_by_chip.values())
        / len(chips) / 1e9,
        program_s=program_s, program_in_serve_s=program_in_serve_s,
        span_s=span_s, busy_in_span_s=busy_in_span_s,
        device_ops=[[n, s] for n, s in sorted(ev.op_s.items(),
                                               key=lambda kv: -kv[1])[:10]],
        idle_gaps=[[_innermost(spans, (s + e) / 2), (e - s) / 1e9]
                   for s, e in idle])
