"""One cell of the benchmark: build it once, then run it for a seed.

Set-up builds the program under test from the cell's files: a
``ServeEngine`` over the configuration, holding weights that the
benchmark draws from the seed (the reference's ``make_params``, one jitted
call on the device), every compiled shape that the cell's traffic can
reach, and a ``KermitSession`` bound to a ``ServeExecutor`` over that
engine.  The loop then runs on warm-up traffic until its first Plan
search has committed.  Only then does the measured window start.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from chipbench import registry, traffic as T
from chipbench.client import Client

# a traced run records the last seconds of the window only: the profiler
# writes some 10 MB per second of decoding, and stopping it stalls the host
# while it writes, so it stops when the window closes
TRACE_SECONDS = 10.0
# the schedules of the warm-up and of the window (due times, prompt and output
# lengths), and the telemetry noise the executor adds, are drawn from this
# fixed seed, so every run does the same work and the loop's first search
# comes at the same window; ``--seed`` draws the weights and every prompt's
# tokens.  Drawn from ``--seed``, the order of lengths alone moved
# ``ttft_p90_s`` by 31% between seeds on one TPU v5e, against about 2%
# between two runs of one seed.
SCHEDULE_SEED = 0x5EED


def model_config(program: dict):
    """The program's ``ModelConfig`` for a configuration file's block."""
    from repro.configs.base import ModelConfig, SSMConfig
    kw = dict(program)
    if "ssm" in kw:
        kw["ssm"] = SSMConfig(**kw["ssm"])
    return ModelConfig(**kw)


class CompileClock:
    """Seconds of backend compilation and persistent-cache hits, from
    ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.count,
                "cache_hits": self.cache_hits}


def _diff(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


class Cell:
    def __init__(self, name: str, root: str = registry.ROOT):
        import jax
        self.name = name
        self.cell = registry.cell(name, root)
        self.config = registry.config(self.cell["config"], root)
        self.mix = registry.traffic(self.cell["traffic"], root)
        self.ref = registry.reference(self.config["reference"], root)
        self.p = self.config["program"]
        self.mcfg = model_config(self.p)
        self.clock = CompileClock()
        self._make_params = jax.jit(lambda key: self.ref.make_params(
            self.p, key))
        self.engine = None

    # -- set-up ------------------------------------------------------------

    def tunables(self, **kw):
        from repro.configs.base import DEFAULT_TUNABLES
        return DEFAULT_TUNABLES.replace(**{**self.cell["initial_tunables"],
                                           **kw})

    def weights(self, seed: int):
        import jax
        key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                                 seed >> 31)
        return jax.block_until_ready(self._make_params(key))

    def build_engine(self, seed: int):
        """The program's engine over the benchmark's weights for ``seed``."""
        import jax
        from repro.kermit.serving import ServeEngine
        from repro.models import model as M
        params = self.weights(seed)
        want = jax.eval_shape(lambda k: M.init(k, self.mcfg),
                              jax.random.PRNGKey(0))
        got = jax.eval_shape(lambda: params)
        if jax.tree_util.tree_structure(want) != \
                jax.tree_util.tree_structure(got) or any(
                    (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                    zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got))):
            raise RuntimeError("the reference's weights do not match the "
                               "program's parameter layout")
        init, M.init = M.init, (lambda key, cfg: params)
        try:
            self.engine = ServeEngine(self.mcfg, seed=seed & 0x7FFFFFFF,
                                      initial=self.tunables())
        finally:
            M.init = init
        if self.engine.params is not params:
            raise RuntimeError("ServeEngine did not take the given weights")
        return self.engine

    def set_weights(self, seed: int):
        self.engine.params = None
        gc.collect()
        self.engine.params = self.weights(seed)

    def shapes(self) -> list:
        """(serve_batch, prompt bucket, decode steps) that reach every
        compiled program the cell's traffic can use."""
        lo, hi = T.output_range(self.mix)
        space = self.cell["plan_space"]
        out = []
        for B in space["serve_batch"]:
            for cache_len in space.get("cache_len", [0]):
                for P in T.prompt_buckets(self.mix):
                    tun = self.tunables(serve_batch=B, cache_len=cache_len)
                    caps = {}
                    # the state-space cache has no capacity to grow
                    steps = [lo - 1] if self.p["family"] == "ssm" \
                        else range(lo - 1, hi)
                    for g in steps:
                        caps.setdefault(self.engine.capacity_for(P, g, tun),
                                        g)
                    out += [(tun, P, g) for g in caps.values()]
        return out

    def warm(self, gens) -> dict:
        """Compile and run once every shape in ``shapes()``, and the token
        concatenation of every step count in ``gens``."""
        import jax.numpy as jnp
        c0 = self.clock.snapshot()
        t0 = time.perf_counter()
        batches = sorted({tun.serve_batch for tun, _, _ in self.shapes()})
        for tun, P, g in self.shapes():
            B = tun.serve_batch
            self.engine._batches[(P, B)] = {
                "tokens": jnp.zeros((B, P), jnp.int32)}
            self.engine.serve(batch=B, prompt_len=P, gen=max(g, 1),
                              tunables=tun)
        for B in batches:
            tok = jnp.zeros((B, 1), jnp.int32)
            for s in sorted(set(int(x) for x in gens)):
                jnp.concatenate([tok] * (s + 1), 1).block_until_ready()
        return dict(_diff(c0, self.clock.snapshot()),
                    wall_s=time.perf_counter() - t0)

    # -- one run -------------------------------------------------------------

    def session(self):
        from repro.kermit import (AnalysisConfig, KermitConfig,
                                  KermitSession, KnowledgeConfig,
                                  MonitorConfig, PlanConfig)
        from repro.kermit.serving import (ServeConfig, ServeExecutor,
                                          TrafficGenerator, TrafficPhase)
        import jax.profiler

        class Executor(ServeExecutor):
            """Marks each Plan probe as a host span."""

            def measure(self):
                with jax.profiler.TraceAnnotation("bench.probe"):
                    return super().measure()

            def measure_batch(self, candidates):
                with jax.profiler.TraceAnnotation("bench.probe"):
                    return super().measure_batch(candidates)

        k = self.cell["kermit"]
        lo, hi = T.output_range(self.mix)
        max_ctx = max(T.prompt_buckets(self.mix)) + hi
        ex = Executor(self.engine,
                      TrafficGenerator([TrafficPhase("bench", 0)],
                                       window_size=k["window_size"],
                                       seed=SCHEDULE_SEED),
                      config=ServeConfig(window_size=k["window_size"],
                                         max_context=max_ctx),
                      initial=self.tunables())
        ex._unit = 1.0      # arrivals are in seconds: one unit is a second
        # every shape a probe can reach was compiled and run in set-up, so
        # a probe replays once instead of running each new shape untimed
        # first: every capacity that a warmed (batch, prompt) reaches over
        # the traffic's outputs runs a warmed program (a state-space program
        # is the same at every capacity)
        ex._warm.update((tun, tun.serve_batch, P,
                         self.engine.capacity_for(P, s, tun))
                        for tun, P, _ in self.shapes()
                        for s in range(lo - 1, hi))
        initial = self.tunables()
        cfg = KermitConfig(
            monitor=MonitorConfig(window_size=k["window_size"]),
            analysis=AnalysisConfig(interval=k["analysis_interval"],
                                    min_windows=k["min_windows"]),
            knowledge=KnowledgeConfig(drift_eps=k["drift_eps"]),
            plan=PlanConfig(space=self.cell["plan_space"],
                            default_tunables=initial.as_dict()))
        return ex, KermitSession(cfg, executor=ex)

    def prompts(self, seed: int, n: int, prompt_len) -> list:
        rng = np.random.default_rng([int(seed), 0x70C5])
        return [rng.integers(0, self.p["vocab"], int(L), dtype=np.int32)
                for L in prompt_len[:n]]

    def run(self, seed: int, seconds: float, trace_dir=None, t_start=None,
            knee=None, loop_warmup=True, schedule_seed=SCHEDULE_SEED):
        """Warm up, measure for ``seconds``, follow the window's requests to
        completion.  Returns a dict of everything the metrics read.  Without
        ``loop_warmup`` the window starts before the loop's first search,
        as the readings behind the limits of ``correct`` do
        (``control.py``); ``schedule_seed`` other than the fixed one is for
        the knee sweep (``sweep.py``)."""
        import jax
        from repro.kermit import EventKind
        t_start = time.perf_counter() if t_start is None else t_start
        knee = float(knee or self.cell["knee_rps"])
        k = self.cell["kermit"]
        warm_n = k["window_size"] * k["warmup_windows"]
        warm, win = T.schedule(self.mix, knee, seconds, warm_n, schedule_seed)
        gaps = [1.0 / (float(ph["rate_knee_share"]) * knee)
                for ph in self.mix["phases"]]
        facts = {"warm": self.warm(np.concatenate([warm.gen, win.gen]))}

        ex, session = self.session()
        events = []
        session.subscribe(None, lambda ev: events.append(ev.kind))

        def annotate(name):
            return jax.profiler.TraceAnnotation(name) if trace_dir \
                else contextlib.nullcontext()

        c0 = self.clock.snapshot()
        t0 = time.perf_counter()
        client = Client(self.engine, ex, session,
                        self.prompts(schedule_seed, len(warm),
                                     warm.prompt_len),
                        self.p["vocab"], k["window_size"], annotate)

        def searched():
            st = session.summary()["plugin"]
            return st["global_searches"] + st["local_searches"] > 0

        if loop_warmup:
            client.run(warm.due, warm.prompt_len, warm.gen, warm.phase, gaps,
                       until=searched)
        stats0 = session.summary()["plugin"]
        facts["loop_warmup"] = dict(
            _diff(c0, self.clock.snapshot()), wall_s=time.perf_counter() - t0,
            windows=client.window_index,
            searches=stats0["global_searches"] + stats0["local_searches"],
            committed=self.engine.tunables.serve_batch)
        n_events0 = len(events)

        client.prompts = self.prompts(seed, len(win), win.prompt_len)
        setup_s = time.perf_counter() - t_start
        probe0, applied0 = ex.measure_seconds, ex.applied
        c0 = self.clock.snapshot()
        state = {"traced_to": None, "bytes_in_use": 0}
        t0 = time.perf_counter()

        def on_tick(now, final=False):
            # called between engine calls, so a call lies wholly inside the
            # trace or wholly outside it
            if now < seconds:
                state["bytes_in_use"] = max(state["bytes_in_use"],
                                            bytes_in_use())
            if trace_dir and "traced_from" not in state and (
                    now >= seconds - TRACE_SECONDS or final):
                state["traced_from"] = now
                # the Python tracer records every Python call and slows the
                # host's own work (the loop's analysis) several times over;
                # the host spans are TraceMe annotations and need only the
                # host tracer
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                # made after the start: a span made before it is not recorded
                state["span"] = jax.profiler.TraceAnnotation("bench.window")
                state["span"].__enter__()
            if (now >= seconds or final) and "closed_at" not in state:
                state["closed_at"] = now
                state["compile_in_window"] = _diff(c0, self.clock.snapshot())
                state["probe_s"] = ex.measure_seconds - probe0
                if trace_dir:
                    state["traced_to"] = now
                    state["span"].__exit__(None, None, None)
                    jax.profiler.stop_trace()

        seg = client.run(win.due, win.prompt_len, win.gen, win.phase, gaps,
                         t0=t0, on_tick=on_tick)
        on_tick(time.perf_counter() - t0, final=True)
        stats = session.summary()["plugin"]
        kinds = events[n_events0:]
        facts["loop_window"] = {
            "analyses": kinds.count(EventKind.ANALYSIS.value),
            "retunes": kinds.count(EventKind.RETUNE.value),
            "searches": stats["global_searches"] + stats["local_searches"]
            - facts["loop_warmup"]["searches"],
            "evaluations": stats["evaluations"],
            "failed_searches": stats["failed_searches"],
            "applied": ex.applied - applied0,
            "committed": self.engine.tunables.as_dict()["serve_batch"]}
        facts["compile_in_window"] = state["compile_in_window"]
        # what the window holds between engine calls (weights, the engine's
        # buffers); the process's peak, stamped as memory_peak_bytes, is
        # reached in set-up while every shape of the Plan space is warmed
        facts["window_bytes_in_use_max"] = state["bytes_in_use"]
        # each engine call of the window, so that a slow run shows which
        # call was slow and at what shape
        facts["calls"] = {
            "columns": ["t_dispatch_s", "batch", "prompt_len", "capacity",
                        "steps", "prefill_s", "decode_s", "requests"],
            "rows": [[c.t_dispatch, c.batch, c.prompt_len, c.capacity,
                      c.steps, c.prefill_s, c.decode_s, len(c.requests)]
                     for c in seg.calls if c.t_dispatch < seconds]}
        facts["backlog_at_close"] = int(np.sum(
            (win.due < seconds) & ~_dispatched_before(seg, seconds,
                                                      len(win))))
        facts["generator_late_s"] = seg.lateness_s
        facts["clock_excess_s"] = seg.clock_excess_s
        return {"setup_s": setup_s, "seconds": seconds,
                "traced": (state.get("traced_from"), state["traced_to"]),
                "window": win, "seg": seg,
                "probe_s": state["probe_s"], "facts": facts,
                "session": session, "executor": ex}


def bytes_in_use() -> int:
    """Bytes held on the fullest local device now (0 where JAX keeps no
    count, as on the CPU)."""
    import jax
    return int(max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.local_devices()))


def _dispatched_before(seg, seconds, n) -> np.ndarray:
    out = np.zeros(n, bool)
    for c in seg.calls:
        if c.t_dispatch < seconds:
            out[c.requests] = True
    return out
