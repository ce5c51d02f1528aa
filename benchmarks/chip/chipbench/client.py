"""The timed path: an open-loop client in front of ``ServeEngine``, with
the KERMIT session managing it.

The program has no request-level front end, so the batching policy lives
here: whenever the engine is free, the client hands it the requests that
are due, oldest first, up to the ``serve_batch`` the session last applied,
in one ``ServeEngine.serve`` call.  A short batch is padded to
``serve_batch`` with copies of its first row, which count in no statistic.
After every ``window_size`` served requests the client builds that
window's telemetry with the executor's own mapping and hands it to
``KermitSession.step_batch``; a Plan search inside that call replays the
window on the same engine, and requests that fall due meanwhile wait.

Each request is timed from its due time.  Its first token comes at the
call's dispatch plus the engine's own ``prefill_s``; its tokens after the
first come ``decode_s / steps`` apart.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

PAD_TOKEN = 0


@dataclass
class Call:
    """One ``ServeEngine.serve`` call (times in s from the segment start)."""
    t_dispatch: float
    t_end: float
    batch: int
    prompt_len: int
    capacity: int
    requests: np.ndarray        # indices of the real rows' requests
    prefill_s: float
    decode_s: float
    steps: int


@dataclass
class Step:
    """One ``KermitSession.step_batch`` call."""
    t_start: float
    wall_s: float
    probe_s: float
    windows: int


@dataclass
class Segment:
    """What one schedule segment (warm-up or window) produced."""
    calls: list = field(default_factory=list)
    steps: list = field(default_factory=list)
    ttft: np.ndarray = None
    tpot: np.ndarray = None
    t_done: np.ndarray = None
    generated: dict = field(default_factory=dict)   # request -> tokens
    rows: dict = field(default_factory=dict)        # request -> prompt row
    lateness_s: float = 0.0         # worst dispatch delay past an idle wait
    clock_excess_s: float = 0.0     # worst prefill_s + decode_s - wall


class Client:
    """Serves a schedule segment through the engine and the session."""

    def __init__(self, engine, executor, session, prompts, vocab: int,
                 window_size: int, annotate):
        self.engine, self.ex, self.session = engine, executor, session
        self.prompts = prompts          # request -> np.int32 prompt tokens
        self.vocab = vocab
        self.W = window_size
        self.annotate = annotate        # name -> context manager
        self.window_index = 0
        self._pending_window: list = []

    def batch_tokens(self, reqs, batch: int, prompt_len: int) -> np.ndarray:
        """Rows of the call: each prompt left-padded to ``prompt_len``."""
        rows = np.full((batch, prompt_len), PAD_TOKEN, np.int32)
        for r, i in enumerate(reqs):
            p = self.prompts[i]
            rows[r, prompt_len - len(p):] = p
        rows[len(reqs):] = rows[0]
        return rows

    def serve(self, reqs, due, prompt_len, gen, t0, seg: Segment):
        import jax.numpy as jnp
        tun = self.ex.current
        B = int(tun.serve_batch)
        n = len(reqs)
        P = int(prompt_len[reqs].max())
        g = np.concatenate([gen[reqs], np.full(B - n, gen[reqs].min())])
        rows = self.batch_tokens(reqs, B, P)
        # the engine takes its prompt batch from this table
        self.engine._batches[(P, B)] = {"tokens": jnp.asarray(rows)}
        t_dispatch = time.perf_counter() - t0
        with self.annotate("bench.serve"):
            rep = self.engine.serve(batch=B, prompt_len=P, gen=g,
                                    tunables=tun)
        t_end = time.perf_counter() - t0
        seg.clock_excess_s = max(seg.clock_excess_s, rep.prefill_s
                                 + rep.decode_s - (t_end - t_dispatch))
        seg.calls.append(Call(t_dispatch, t_end, B, P, rep.capacity,
                              np.asarray(reqs), rep.prefill_s, rep.decode_s,
                              rep.steps))
        step_s = rep.decode_s / max(rep.steps, 1)
        for r, i in enumerate(reqs):
            seg.ttft[i] = t_dispatch + rep.prefill_s - due[i]
            seg.tpot[i] = step_s
            seg.t_done[i] = t_end
            seg.generated[i] = rep.generated[r, :gen[i] + 1]
            seg.rows[i] = rows[r]
        return B

    def feed_loop(self, reqs, due, prompt_len, gen, phase, t0,
                  seg: Segment, phase_gap):
        """Queue served requests; hand each full window to the session."""
        self._pending_window.extend(reqs)
        while len(self._pending_window) >= self.W:
            win, self._pending_window = (self._pending_window[:self.W],
                                         self._pending_window[self.W:])
            self.step(np.asarray(win), due, prompt_len, gen, phase, t0, seg,
                      phase_gap)

    def step(self, win, due, prompt_len, gen, phase, t0, seg, phase_gap):
        from repro.kermit.serving.traffic import RequestWindow
        lat = seg.t_done[win] - due[win]
        span = max(float(seg.t_done[win].max() - due[win].min()), 1e-9)
        ph = int(phase[win[-1]])
        rw = RequestWindow(
            index=self.window_index, phase=str(ph), phase_index=ph,
            arrivals=due[win] - due[win].min(), tenant=np.zeros(len(win),
                                                                np.int64),
            prompt_len=prompt_len[win], gen=gen[win], gap=phase_gap[ph])
        self.window_index += 1
        rows = self.ex._telemetry(rw, {"latencies": lat,
                                       "tokens_per_s": float(np.sum(
                                           gen[win] + 1)) / span})
        self.ex._probe = rw
        probe0 = self.ex.measure_seconds
        t_start = time.perf_counter() - t0
        with self.annotate("bench.step_batch"):
            self.session.step_batch(rows)
        seg.steps.append(Step(t_start, time.perf_counter() - t0 - t_start,
                              self.ex.measure_seconds - probe0, 1))

    def run(self, due, prompt_len, gen, phase, phase_gap, t0=None,
            on_tick=None, until=None) -> Segment:
        """Serve every request of a segment, or, with ``until``, stop at the
        first window boundary where ``until()`` holds; return what
        happened."""
        n = len(due)
        seg = Segment(ttft=np.full(n, np.inf), tpot=np.full(n, np.inf),
                      t_done=np.full(n, np.inf))
        t0 = time.perf_counter() if t0 is None else t0
        nxt = 0
        queue: list = []
        while nxt < n or queue:
            now = time.perf_counter() - t0
            while nxt < n and due[nxt] <= now:
                queue.append(nxt)
                nxt += 1
            if on_tick is not None:
                on_tick(now)
            if not queue:
                with self.annotate("bench.wait"):
                    time.sleep(max(due[nxt] - now, 0.0))
                seg.lateness_s = max(seg.lateness_s, time.perf_counter()
                                     - t0 - due[nxt])
                continue
            B = int(self.ex.current.serve_batch)
            take, queue = queue[:B], queue[B:]
            self.serve(take, due, prompt_len, gen, t0, seg)
            self.feed_loop(take, due, prompt_len, gen, phase, t0, seg,
                           phase_gap)
            if until is not None and not self._pending_window and until():
                break
        return seg
