"""Smoke run of the KERMIT loop around qwen2-1.5b serving on one TPU chip.

    python chip_smoke.py

Everything runs in this one process, in four phases:

  device    the first JAX device must be a TPU; anything else exits nonzero
  kernels   compiled Pallas kernels against their references at real
            widths: flash attention (qwen2-1.5b heads), the ε-neighbour
            adjacency (bit for bit, N=64 and N=2048) and the SSD scan
            (mamba2-1.3b heads)
  serving   ServeEngine at the full published qwen2-1.5b width (random
            weights from seed 0): finite logits, tokens in vocab, greedy
            prefill-then-decode agreeing with one forward pass over the
            extended sequence
  loop      KermitSession closing the MAPE-K loop around that engine under
            diurnal traffic: at least one Analyse pass on the compiled
            neighbour kernel, at least one Plan search, no failed search

Any failed check raises and the script exits nonzero.  Earlier lines are
facts about the run (device, compile cache, per-phase wall and compile
seconds, engine counters, peak device memory), not benchmark metrics.  The
last stdout line is {"ok": true, "device": {platform, kind, count}}.
"""
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def check(cond, msg):
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


class CompileClock:
    """Sums JAX's backend-compile durations (cache lookups included) and
    counts persistent-cache hits, so each phase reports its compile
    seconds apart from its wall time."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def phase(name, clock, fn):
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    out = fn()
    print(json.dumps({"phase": name,
                      "wall_s": time.perf_counter() - t0,
                      "compile_s": clock.seconds - c0,
                      "compile_cache_hits": clock.cache_hits - h0}),
          flush=True)
    return out


def device_check(jax):
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found: the first JAX device is "
            f"{dev.platform!r} ({dev.device_kind}); this script only runs "
            f"on a chip")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# -- kernels ------------------------------------------------------------------


def kernel_parity(jax, jnp):
    from repro.configs.registry import get_config
    from repro.kernels import dispatch, ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.pairdist import neighbor_adjacency, unpack_bits
    from repro.kernels.ssd_scan import ssd
    from repro.models.mamba2 import ssd_chunked

    check(dispatch.resolve("auto") == "pallas",
          f"kernel dispatch resolves to {dispatch.resolve('auto')!r}")
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    out = {}

    # flash attention at qwen2-1.5b head shapes; bf16 in and out
    cfg = get_config("qwen2-1.5b")
    B, S = 4, 1024
    q = jax.random.normal(keys[0], (B, S, cfg.n_heads, cfg.hd), jnp.bfloat16)
    k = jax.random.normal(keys[1], (B, S, cfg.n_kv_heads, cfg.hd),
                          jnp.bfloat16)
    v = jax.random.normal(keys[2], (B, S, cfg.n_kv_heads, cfg.hd),
                          jnp.bfloat16)
    got = np.asarray(flash_attention(q, k, v, causal=True), np.float32)
    want = np.asarray(jax.jit(ref.attention_ref)(q, k, v), np.float32)
    err = np.abs(got - want)
    check(np.all(err <= 3e-2 + 3e-2 * np.abs(want)),
          f"flash attention vs attention_xla: max abs err {err.max()}")
    out["flash_max_abs_err"] = float(err.max())

    # ε-neighbour adjacency, bit for bit against the XLA twin and the dense
    # oracle.  Points sit on a 1/4 grid so every distance is exact in f32
    # whatever the summation order, and many pairs land exactly on ε².
    rng = np.random.default_rng(0)
    for n in (64, 2048):
        centers = rng.integers(-6, 7, (8, 16))
        x = centers[rng.integers(0, 8, n)] + rng.integers(-2, 3, (n, 16)) / 4
        x = jnp.asarray(x, jnp.float32)
        c_k, p_k = neighbor_adjacency(x, 2.0)
        c_x, p_x = neighbor_adjacency(x, 2.0, impl="xla")
        check(np.array_equal(np.asarray(c_k), np.asarray(c_x)),
              f"neighbour counts differ from the XLA twin at N={n}")
        check(np.array_equal(np.asarray(p_k), np.asarray(p_x)),
              f"packed adjacency differs from the XLA twin at N={n}")
        dense = np.asarray(ref.ref_adjacency(x, 2.0))
        check(np.array_equal(np.asarray(unpack_bits(p_k))[:n, :n], dense),
              f"packed adjacency differs from the dense oracle at N={n}")
        out[f"adjacency_density_n{n}"] = float(dense.mean())

    # SSD scan at mamba2-1.3b head shapes; reference in f32 at highest
    # matmul precision, like the kernel's own dots
    mcfg = get_config("mamba2-1.3b")
    s = mcfg.ssm
    B, S = 2, 2 * s.chunk
    H = s.expand * mcfg.d_model // s.head_dim
    x = jax.random.normal(keys[3], (B, S, H, s.head_dim), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(keys[4], (B, S, H)) - 4.0)
    A = -jnp.exp(0.5 * jax.random.normal(keys[5], (H,)))
    Bm = (0.3 * jax.random.normal(keys[6], (B, S, s.n_groups, s.d_state))
          ).astype(jnp.bfloat16)
    Cm = (0.3 * jax.random.normal(keys[7], (B, S, s.n_groups, s.d_state))
          ).astype(jnp.bfloat16)
    y_k, s_k = ssd(x, dt, A, Bm, Cm, chunk=s.chunk)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        y_r, s_r = jax.jit(ssd_chunked, static_argnums=5)(
            x.astype(f32), dt, A, Bm.astype(f32), Cm.astype(f32), s.chunk)
    for name, a, b in (("y", y_k, y_r), ("state", s_k, s_r)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        rel = float(np.abs(a - b).max() / np.abs(b).max())
        check(rel <= 1e-3, f"SSD {name} vs ssd_chunked: max err / max "
              f"|ref| = {rel}")
        out[f"ssd_{name}_rel_err"] = rel
    return out


# -- serving ------------------------------------------------------------------


def serving(jax, jnp, engine):
    from repro.configs.base import ShapeSpec
    from repro.models import model as M

    cfg = engine.cfg
    out = {}
    for batch, prompt, gen in ((8, 64, 16), (8, 64, 16), (4, 128, 8)):
        rep = engine.serve(batch=batch, prompt_len=prompt, gen=gen)
        toks = rep.generated
        check(toks.shape == (batch, gen + 1), f"generated shape {toks.shape}")
        check(np.all((toks >= 0) & (toks < cfg.vocab)),
              "a generated token is outside the vocabulary")

    # greedy prefill-then-decode vs one forward pass over the extended
    # sequence: every decoded token must be the forward pass's argmax up to
    # bf16 near-ties (within 5% of the row's largest |logit|)
    B, P, G = 8, 64, 16
    rep = engine.serve(batch=B, prompt_len=P, gen=G)
    prompt = M.make_batch(jax.random.PRNGKey(engine.seed), cfg,
                          ShapeSpec("pf", P, B, "prefill"))["tokens"]
    ext = jnp.concatenate([prompt, jnp.asarray(rep.generated[:, :G])], 1)
    forward = jax.jit(lambda p, b: M.forward(p, cfg, b,
                                             engine.tunables)[0])
    logits = forward(engine.params, {"tokens": ext})[:, P - 1:, :cfg.vocab]
    logits = np.asarray(logits, np.float32)                   # (B, G+1, V)
    check(np.all(np.isfinite(logits)), "non-finite logits")
    chosen = np.take_along_axis(logits, rep.generated[..., None], -1)[..., 0]
    best = logits.max(-1)
    tol = 5e-2 * np.abs(logits).max(-1)
    check(np.all(chosen >= best - tol),
          f"decoded tokens disagree with the forward pass: worst gap "
          f"{float((best - chosen - tol).max())} beyond tolerance")
    agree = float(np.mean(logits.argmax(-1) == rep.generated))
    check(agree >= 0.5, f"exact greedy agreement only {agree}")
    out["greedy_exact_agreement"] = agree
    out["decode_worst_gap_over_row_max"] = float(
        ((best - chosen) / np.abs(logits).max(-1)).max())
    return out


# -- the closed loop ----------------------------------------------------------


def closed_loop(engine):
    from repro.configs.base import Tunables
    from repro.kermit import (AnalysisConfig, EventKind, KermitConfig,
                              KermitSession, KnowledgeConfig, MonitorConfig,
                              PlanConfig)
    from repro.kermit.serving import (SERVE_SPACE, ServeExecutor,
                                      TrafficGenerator, run_serving_session)
    from repro.kernels import dispatch, pairdist

    check(dispatch.resolve("auto") == "pallas",
          "the Analyse phase would not run the compiled kernel")
    initial = Tunables(serve_batch=8, cache_len=64)
    space = {"serve_batch": [b for b in SERVE_SPACE["serve_batch"]
                             if b >= 4],
             "cache_len": [64]}
    traffic = TrafficGenerator.diurnal(window_size=8, seed=0,
                                       night_windows=10, day_windows=10)
    ex = ServeExecutor(engine, traffic, initial=initial)
    cfg = KermitConfig(
        monitor=MonitorConfig(window_size=8),
        analysis=AnalysisConfig(interval=6, min_windows=6),
        knowledge=KnowledgeConfig(drift_eps=0.45),
        plan=PlanConfig(space=space, default_tunables=initial.as_dict()))
    kernel_builds = pairdist._neighbor_adjacency_pallas._cache_size()
    kinds = []
    with KermitSession(cfg, executor=ex) as session:
        session.subscribe(None, lambda ev: kinds.append(ev.kind))
        final = run_serving_session(session, ex)
        stats = session.summary()["plugin"]

    analyses = kinds.count(EventKind.ANALYSIS.value)
    searches = stats["global_searches"] + stats["local_searches"]
    check(analyses >= 1, "no Analyse pass ran")
    check(pairdist._neighbor_adjacency_pallas._cache_size() > kernel_builds,
          "the Analyse phase never called the compiled neighbour kernel")
    check(searches >= 1, f"no Plan search completed: {stats}")
    check(stats["failed_searches"] == 0, f"failed searches: {stats}")
    check(len(ex.window_log) == traffic.n_windows,
          f"served {len(ex.window_log)} of {traffic.n_windows} windows")
    return {"windows": len(ex.window_log), "analyses": analyses,
            "searches": searches, "evaluations": stats["evaluations"],
            "failed_searches": stats["failed_searches"],
            "retunes": kinds.count(EventKind.RETUNE.value),
            "final": {k: getattr(final, k) for k in space}}


def main():
    import jax
    import jax.numpy as jnp

    device = device_check(jax)
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.configs.registry import get_config
    from repro.kermit.serving import ServeEngine
    from repro.runtime.compile_cache import enable_compile_cache

    print(json.dumps({"device": device,
                      "compile_cache_dir": enable_compile_cache()}),
          flush=True)
    clock = CompileClock(jax)
    facts = {"kernels": phase("kernels", clock,
                              lambda: kernel_parity(jax, jnp))}
    engine = phase("engine_build", clock,
                   lambda: ServeEngine(get_config("qwen2-1.5b")))
    facts["serving"] = phase("serving", clock,
                             lambda: serving(jax, jnp, engine))
    facts["loop"] = phase("loop", clock, lambda: closed_loop(engine))
    facts["engine_stats"] = dict(engine.stats)
    facts["compile_s_total"] = clock.seconds
    facts["peak_bytes_in_use"] = \
        jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(json.dumps(facts, default=str), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
