"""ServeEngine — the real inference stack as a reconfigurable resource.

``launch/serve.py``'s original ``serve_batch`` re-initialized parameters and
re-jitted the prefill/decode steps on every call, which made it unusable as a
KERMIT Execute boundary: a configuration search evaluates dozens of
candidates, and paying ``M.init`` + two traces per evaluation drowns the
signal being measured.  The engine holds the model once and caches compiled
steps per configuration:

  params           initialized once per (cfg, seed) — identical keys to the
                   legacy launcher, so greedy decodes are bit-identical
  prefill/decode   ``jax.jit`` closures cached per effective Tunables; a
                   repeated knob evaluation reuses the compiled step (XLA
                   still specializes per input shape inside each entry)
  apply/serve      ``apply(tunables)`` stages a configuration;
                   ``serve(...)`` runs batched prefill + greedy decode under
                   it and reports wall-clock timings + per-request
                   completion times

Serving-specific knobs (``configs/base.Tunables``):

  serve_batch    decode batch size — owned by the executor's chunking, the
                 engine just serves whatever batch it is handed
  prefill_chunk  attention q-chunk override for the prefill trace (0 =
                 inherit ``attn_q_chunk``)
  cache_len      KV-cache capacity rounding multiple (0 = exact fit).
                 Decode masks attention by true position (``kv_len=pos+1``),
                 so over-allocated capacity is numerically free and lets
                 phases with different prompt lengths share compiled shapes
  cache_dtype    KV storage precision ("auto" = model dtype).  Decode
                 already casts written keys/values into the cache dtype, so
                 a bfloat16 cache needs no model changes
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from repro.configs.base import (DEFAULT_TUNABLES, ModelConfig, ShapeSpec,
                                Tunables, reduced)
from repro.configs.registry import get_config
from repro.runtime import spans

# cache arrays grown/cast between prefill and decode (attention families)
_CACHE_KV_NAMES = ("k", "v", "k0", "v0")


def tiny_config(arch: str, **kw) -> ModelConfig:
    """CPU-CI-sized family-faithful config (2 layers, d_model 64) — the
    model the serving scenarios/benchmarks manage."""
    cfg = reduced(get_config(arch))
    small = dict(n_layers=2, d_model=64, n_heads=2,
                 n_kv_heads=1 if cfg.n_kv_heads == 1 else 2,
                 d_ff=128, vocab=256, head_dim=32, dtype="float32")
    if cfg.hybrid_period:
        small["hybrid_period"] = 2
        small["n_layers"] = 5
    if cfg.enc_layers:
        small["enc_layers"] = 2
    if cfg.num_patches:
        small["num_patches"] = 8
    small.update(kw)
    return cfg.replace(**small)


@dataclass
class ServeReport:
    """One engine call: timings plus per-request completion estimates."""
    batch: int
    prompt_len: int
    gen: np.ndarray               # (B,) decoded tokens per request
    capacity: int                 # compiled KV capacity (prompt + padding)
    prefill_s: float
    decode_s: float
    steps: int                    # decode steps run (= max(gen))
    generated: np.ndarray         # (B, 1 + steps) greedy tokens
    completion_s: np.ndarray = field(default=None)  # (B,) service latency

    def __post_init__(self):
        if self.completion_s is None:
            # decode cost attributed uniformly per step: a request that
            # needs g tokens completes after g steps of the shared batch
            # (as a share of decode_s, so the longest ends at total_s exactly)
            share = np.asarray(self.gen, np.float64) / max(self.steps, 1)
            self.completion_s = self.prefill_s + self.decode_s * share

    @property
    def total_s(self) -> float:
        return self.prefill_s + self.decode_s

    @property
    def tokens(self) -> int:
        return int(np.sum(self.gen)) + self.batch   # + first prefill token


class ServeEngine:
    """Holds params + jit-cached prefill/decode steps for one model config.

    ``apply(tunables)`` stages the active configuration; ``serve`` accepts an
    explicit ``tunables=`` override so batched candidate probes never move
    the applied state (the Execute-protocol probe contract).
    """

    def __init__(self, cfg: ModelConfig, *, seed: int = 0,
                 initial: Tunables = DEFAULT_TUNABLES):
        import jax

        from repro.models import model as M
        self.cfg = cfg
        self.seed = int(seed)
        self._key = jax.random.PRNGKey(self.seed)
        self.params = M.init(self._key, cfg)
        self.tunables = initial
        self._prefill: dict = {}     # effective Tunables -> jitted prefill
        self._decode: dict = {}      # Tunables -> jitted decode
        self._batches: dict = {}     # (prompt_len, batch) -> token batch
        self.stats = {"prefill_builds": 0, "decode_builds": 0,
                      "serve_calls": 0, "decode_steps": 0}

    # -- configuration ------------------------------------------------------

    def apply(self, tunables: Tunables) -> None:
        """Stage ``tunables`` as the engine's active configuration."""
        self.tunables = tunables

    # -- compiled-step caches ----------------------------------------------

    def _prefill_effective(self, tun: Tunables) -> Tunables:
        if tun.prefill_chunk > 0:
            return tun.replace(attn_q_chunk=tun.prefill_chunk)
        return tun

    def prefill_step(self, tun: Tunables):
        import jax

        from repro.train.step import make_prefill_step
        eff = self._prefill_effective(tun)
        fn = self._prefill.get(eff)
        if fn is None:
            fn = jax.jit(make_prefill_step(self.cfg, eff))
            self._prefill[eff] = fn
            self.stats["prefill_builds"] += 1
        return fn

    def decode_step(self, tun: Tunables):
        import jax

        from repro.train.step import make_serve_step
        fn = self._decode.get(tun)
        if fn is None:
            fn = jax.jit(make_serve_step(self.cfg, tun),
                         donate_argnums=(1,))
            self._decode[tun] = fn
            self.stats["decode_builds"] += 1
        return fn

    def _token_batch(self, prompt_len: int, batch: int):
        from repro.models import model as M
        key = (prompt_len, batch)
        b = self._batches.get(key)
        if b is None:
            b = M.make_batch(self._key, self.cfg,
                             ShapeSpec("pf", prompt_len, batch, "prefill"))
            self._batches[key] = b
        return b

    # -- the serve path -----------------------------------------------------

    def capacity_for(self, prompt_len: int, max_gen: int,
                     tun: Optional[Tunables] = None) -> int:
        tun = tun or self.tunables
        cap = prompt_len + max_gen
        if tun.cache_len > 0:
            cap = -(-cap // tun.cache_len) * tun.cache_len
        return cap

    def serve(self, *, batch: int, prompt_len: int,
              gen: int | Sequence[int],
              tunables: Optional[Tunables] = None) -> ServeReport:
        """Batched prefill + greedy decode.  ``gen`` is either one length
        for the whole batch or a per-request vector; the batch runs
        ``max(gen)`` steps and each request's completion time is attributed
        at its own length."""
        tun = tunables if tunables is not None else self.tunables
        gen_vec = np.full(batch, int(gen), np.int64) \
            if np.isscalar(gen) else np.asarray(gen, np.int64)
        if gen_vec.shape != (batch,):
            raise ValueError(f"gen vector shape {gen_vec.shape} != ({batch},)")
        steps = int(gen_vec.max())
        capacity = self.capacity_for(prompt_len, steps, tun)
        with spans.span("kermit.serve", batch=int(batch),
                        prompt_len=int(prompt_len), capacity=capacity,
                        steps=steps):
            return self._serve(tun, batch, prompt_len, gen_vec, steps,
                               capacity)

    def _serve(self, tun: Tunables, batch: int, prompt_len: int,
               gen_vec: np.ndarray, steps: int,
               capacity: int) -> ServeReport:
        import jax
        import jax.numpy as jnp

        from repro.models import model as M

        pad = capacity - prompt_len
        prefill = self.prefill_step(tun)
        decode = self.decode_step(tun)
        b = self._token_batch(prompt_len, batch)
        cache_dt = None if tun.cache_dtype == "auto" \
            else jnp.dtype(tun.cache_dtype)
        seq_axis = M.cache_seq_axis(self.cfg)

        def grow(path, a):
            name = str(path[-1].key) if hasattr(path[-1], "key") else ""
            if name in _CACHE_KV_NAMES and a.ndim >= 4:
                padding = [(0, 0)] * a.ndim
                padding[seq_axis] = (0, pad)
                a = jnp.pad(a, padding)
                if cache_dt is not None:
                    a = a.astype(cache_dt)
            return a

        with spans.timed("kermit.prefill") as prefill_t:
            logits, cache = prefill(self.params, b)
            with spans.span("kermit.cache_grow"):
                cache = jax.tree_util.tree_map_with_path(grow, cache)
            jax.block_until_ready(logits)

        # greedy over the real vocabulary: the embedding's padding rows
        # (vocab_padded > vocab) are never a token
        vocab = self.cfg.vocab
        tokens = jnp.argmax(logits[:, -1, :vocab], -1)[:, None].astype(
            jnp.int32)
        out = [tokens]
        with spans.timed("kermit.decode", steps=steps) as decode_t:
            for i in range(steps):
                with spans.span("kermit.decode_step"):
                    step_batch = {"tokens": tokens,
                                  "pos": jnp.asarray(prompt_len + i,
                                                     jnp.int32)}
                    logits, cache = decode(self.params, cache, step_batch)
                    tokens = jnp.argmax(logits[:, -1, :vocab],
                                        -1)[:, None].astype(jnp.int32)
                out.append(tokens)
            with spans.span("kermit.decode_wait"):
                jax.block_until_ready(tokens)
        with spans.span("kermit.collect"):
            generated = np.asarray(jnp.concatenate(out, 1))

        self.stats["serve_calls"] += 1
        self.stats["decode_steps"] += steps
        return ServeReport(
            batch=batch, prompt_len=prompt_len, gen=gen_vec,
            capacity=capacity, prefill_s=prefill_t.seconds,
            decode_s=decode_t.seconds, steps=steps, generated=generated)

    def serve_legacy(self, batch: int, prompt_len: int, gen: int,
                     tun: Tunables) -> dict:
        """The ``launch/serve.py`` result dict, unchanged (CLI contract)."""
        rep = self.serve(batch=batch, prompt_len=prompt_len, gen=gen,
                         tunables=tun)
        return {
            "prefill_s": rep.prefill_s,
            "decode_s": rep.decode_s,
            "decode_tok_per_s": batch * gen / rep.decode_s,
            "generated": rep.generated.tolist(),
        }


# -- process-wide engine cache (the launcher's entry point) ------------------

_ENGINES: "OrderedDict" = OrderedDict()
_ENGINE_CACHE_MAX = 8


def get_engine(cfg: ModelConfig, seed: int = 0, *,
               max_engines: int | None = None) -> ServeEngine:
    """The shared engine for (cfg, seed): params are initialized and steps
    compiled once per process, however many ``serve_batch`` calls run.

    The cache is LRU-bounded: a hit refreshes the entry's recency and an
    insert past the bound evicts the least-recently-used engine (params +
    compiled steps become collectable).  ``max_engines`` overrides the
    process-wide bound for this call — a fleet serving many model configs
    can widen it, a memory-tight host can pin it to 1."""
    bound = _ENGINE_CACHE_MAX if max_engines is None else int(max_engines)
    if bound < 1:
        raise ValueError(f"max_engines must be >= 1, got {max_engines}")
    key = (cfg, int(seed))
    eng = _ENGINES.get(key)
    if eng is not None:
        _ENGINES.move_to_end(key)
    else:
        eng = ServeEngine(cfg, seed=seed)
        _ENGINES[key] = eng
    while len(_ENGINES) > bound:
        _ENGINES.popitem(last=False)
    return eng
