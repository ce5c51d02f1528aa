"""Unified model interface dispatched on ``cfg.family``.

Functions:
  init(key, cfg)                       -> params
  loss_fn(params, cfg, batch, tun)     -> (loss, metrics)
  prefill(params, cfg, batch, tun)     -> (logits, cache)
  decode(params, cfg, batch, cache, tun) -> (logits, new_cache)
  init_cache(cfg, batch, seq)          -> cache pytree (zeros; eval_shape-able)
  cache_seq_axis(cfg)                  -> sequence axis of the k/v cache leaves
  input_specs(cfg, shape)              -> {name: ShapeDtypeStruct} for the batch
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import transformer as T
from repro.models import encdec as ED
from repro.models import ssm_lm as S


def init(key, cfg: ModelConfig):
    if cfg.family == "encdec":
        return ED.init(key, cfg)
    if cfg.family == "ssm":
        return S.init_mamba(key, cfg)
    if cfg.family == "hybrid":
        return S.init_zamba(key, cfg)
    return T.init(key, cfg)


def forward(params, cfg, batch, tun, *, return_cache=False):
    if cfg.family == "encdec":
        return ED.forward(params, cfg, batch, tun, return_cache=return_cache)
    if cfg.family == "ssm":
        return S.forward_mamba(params, cfg, batch, tun, return_cache=return_cache)
    if cfg.family == "hybrid":
        return S.forward_zamba(params, cfg, batch, tun, return_cache=return_cache)
    return T.forward(params, cfg, batch, tun, return_cache=return_cache)


def cross_entropy(logits, targets, mask, vocab: int | None = None):
    logits = logits.astype(jnp.float32)
    if vocab is not None and logits.shape[-1] > vocab:
        pad = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(pad >= vocab, -1e30, logits)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    return nll.sum() / denom


def loss_fn(params, cfg, batch, tun):
    logits, aux, _ = forward(params, cfg, batch, tun)
    tgt = batch["targets"]
    if cfg.family == "vlm":
        # logits cover [patches | text]; targets/mask cover the full length
        pass
    ce = cross_entropy(logits, tgt, batch["mask"], cfg.vocab)
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux}


def prefill(params, cfg, batch, tun):
    logits, _, cache = forward(params, cfg, batch, tun, return_cache=True)
    return logits[:, -1:], cache


def decode(params, cfg, batch, cache, tun):
    if cfg.family == "encdec":
        return ED.decode_step(params, cfg, batch, cache, tun)
    if cfg.family == "ssm":
        return S.decode_mamba(params, cfg, batch, cache, tun)
    if cfg.family == "hybrid":
        return S.decode_zamba(params, cfg, batch, cache, tun)
    return T.decode_step(params, cfg, batch, cache, tun)


def cache_seq_axis(cfg) -> int:
    """The sequence axis of the ``k``/``v``/``k0``/``v0`` cache leaves,
    counted from the end: the transformer families keep their cache
    sequence-major, (..., S, K, B, hd), the layout their decode step reads;
    the others (..., B, S, K, hd)."""
    return -3 if cfg.family in ("encdec", "ssm", "hybrid") else -4


def init_cache(cfg, batch: int, seq: int):
    if cfg.family == "encdec":
        return ED.init_cache(cfg, batch, seq)
    if cfg.family == "ssm":
        return S.cache_mamba(cfg, batch, seq)
    if cfg.family == "hybrid":
        return S.cache_zamba(cfg, batch, seq)
    return T.init_cache(cfg, batch, seq)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """Batch ShapeDtypeStructs for (cfg, shape). Decode cells additionally
    need the cache — see ``cache_specs``."""
    B, Sq = shape.global_batch, shape.seq_len
    dt = cfg.dtype
    i32 = jnp.int32
    if shape.kind == "decode":
        return {"tokens": _sds((B, 1), i32), "pos": _sds((), i32)}

    if cfg.family == "vlm":
        npt = cfg.num_patches
        d = {"tokens": _sds((B, Sq - npt), i32),
             "patches": _sds((B, npt, cfg.d_model), dt)}
        if shape.kind == "train":
            d["targets"] = _sds((B, Sq), i32)
            d["mask"] = _sds((B, Sq), jnp.float32)
        return d
    if cfg.family == "encdec":
        half = Sq // 2
        d = {"frames": _sds((B, half, cfg.d_model), dt),
             "tokens": _sds((B, half), i32)}
        if shape.kind == "train":
            d["targets"] = _sds((B, half), i32)
            d["mask"] = _sds((B, half), jnp.float32)
        return d
    d = {"tokens": _sds((B, Sq), i32)}
    if shape.kind == "train":
        d["targets"] = _sds((B, Sq), i32)
        d["mask"] = _sds((B, Sq), jnp.float32)
    return d


def cache_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    return jax.eval_shape(lambda: init_cache(cfg, shape.global_batch,
                                             shape.seq_len))


def make_batch(key, cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """Concrete random batch matching input_specs (smoke tests/examples)."""
    specs = input_specs(cfg, shape)
    out = {}
    for k, s in specs.items():
        key, sub = jax.random.split(key)
        if s.dtype == jnp.int32:
            if k == "pos":
                out[k] = jnp.asarray(shape.seq_len - 1, jnp.int32)
            else:
                out[k] = jax.random.randint(sub, s.shape, 0, cfg.vocab, jnp.int32)
        elif k == "mask":
            out[k] = jnp.ones(s.shape, jnp.float32)
        else:
            out[k] = jax.random.normal(sub, s.shape).astype(s.dtype)
    return out
