"""Decoder-only transformer LM covering the dense / moe / vlm families.

Layers are stacked (leading L axis) and applied with lax.scan; the scanned body
is wrapped in jax.checkpoint with the policy chosen by the ``remat`` tunable.
Per-layer heterogeneity (gemma2 alternating local/global windows) rides through
the scan as a per-layer scalar.

The decode KV cache is sequence-major, (L, S, K, B, hd): the layout the TPU
compiler chooses for the decode step's layer loop, so the donated cache enters
and leaves the loop without a relayout.  The loop carries the whole stack and
each layer writes only its new token's keys and values into it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models import moe as MOE
from repro.sharding.rules import maybe_constrain, act_spec

REMAT_POLICY = {
    "none": jax.checkpoint_policies.everything_saveable,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "full": jax.checkpoint_policies.nothing_saveable,
}


def _is_moe_layer(cfg, idx: int) -> bool:
    if cfg.moe is None:
        return False
    if cfg.moe.first_layer_dense and idx == 0:
        return False
    return True


def _dense_ff0(cfg) -> int:
    """FLOP-matched dense FFN width for deepseek's dense first layer."""
    m = cfg.moe
    return (m.top_k + m.num_shared) * m.d_expert


def layer_init(key, cfg, dtype, moe_layer: bool, d_ff: int | None = None):
    ks = jax.random.split(key, 2)
    p = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "attn": L.attn_init(ks[0], cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
    }
    if moe_layer:
        p["moe"] = MOE.moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, d_ff or cfg.d_ff, dtype)
    return p


def init(key, cfg):
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    params = {"embed": L.embed_init(ks[0], cfg.vocab_padded, cfg.d_model, dtype),
              "ln_f": jnp.zeros((cfg.d_model,), dtype)}
    n_scan = cfg.n_layers
    if cfg.moe is not None and cfg.moe.first_layer_dense:
        params["layer0"] = layer_init(ks[1], cfg, dtype, False, _dense_ff0(cfg))
        n_scan -= 1
    lkeys = jax.random.split(ks[2], n_scan)
    params["layers"] = jax.vmap(
        lambda k: layer_init(k, cfg, dtype, cfg.moe is not None))(lkeys)
    if cfg.family == "vlm":
        params["patch_proj"] = L.dense_init(ks[3], cfg.d_model, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["head"] = L.dense_init(ks[4], cfg.d_model, cfg.vocab_padded, dtype)
    return params


def layer_windows(cfg, n: int):
    """Per-layer window scalars: 0 = full attention."""
    idx = jnp.arange(n)
    if cfg.window_pattern == "alternating":
        return jnp.where(idx % 2 == 0, cfg.window, 0).astype(jnp.int32)
    return jnp.full((n,), cfg.window, jnp.int32)


def _cache_write(c, new, layer, pos):
    """Write one token's keys or values, (B,1,K,hd), into the cache ``c`` at
    position ``pos``.  ``c`` is sequence-major: (L,S,K,B,hd) with ``layer``
    the layer to write, or (S,K,B,hd) with ``layer`` None.  Returns the
    cache and the layer's part as (B,S,K,hd), which attention reads."""
    new = new.astype(c.dtype).transpose(1, 2, 0, 3)        # (1,K,B,hd)
    if layer is None:
        c = lax.dynamic_update_slice(c, new, (pos, 0, 0, 0))
        part = c
    else:
        c = lax.dynamic_update_slice(c, new[None], (layer, pos, 0, 0, 0))
        part = lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
    return c, part.transpose(2, 0, 1, 3)


def block_apply(p, x, cfg, tun, *, positions, window, prefix_len=0,
                kv=None, kv_pos=None, kv_len=None, write_pos=None, layer=None):
    """One transformer block. If ``kv``/``write_pos`` given -> decode w/ cache:
    ``kv`` is the (k, v) cache, stacked over layers with ``layer`` this
    block's index, or one layer's own with ``layer`` None (``_cache_write``);
    the new token's keys and values are written into it at ``write_pos`` and
    the updated cache is returned."""
    moe_layer = "moe" in p
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    if write_pos is not None:
        # decode: project single token, write it into the cache, attend
        # over this layer's part of the cache
        q, k1, v1 = L.attn_qkv(p["attn"], h, cfg, positions)
        ck, lk = _cache_write(kv[0], k1, layer, write_pos)
        cv, lv = _cache_write(kv[1], v1, layer, write_pos)
        out = L.attention_xla(q, lk, lv, q_pos=positions, kv_pos=kv_pos,
                              causal=True, window=window, prefix_len=prefix_len,
                              softcap=cfg.attn_softcap, kv_len=kv_len,
                              q_chunk=tun.attn_q_chunk)
        B = x.shape[0]
        out = out.reshape(B, 1, cfg.n_heads * cfg.hd)
        h = jnp.einsum("bsh,hd->bsd", out, p["attn"]["wo"])
        new_kv = (ck, cv)
    else:
        impl = "pallas" if tun.attn_impl == "pallas" else "xla"
        h, new_kv = L.attn_apply(p["attn"], h, cfg, positions=positions,
                                 causal=True, window=window,
                                 prefix_len=prefix_len, q_chunk=tun.attn_q_chunk,
                                 impl=impl, unroll=tun.attn_unroll)
    x = x + h
    x = maybe_constrain(x, act_spec(tun))
    h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    if moe_layer:
        h, aux = MOE.moe_apply(p["moe"], h, cfg,
                               capacity_factor=tun.capacity_factor)
    else:
        h, aux = L.mlp_apply(p["mlp"], h), jnp.zeros((), jnp.float32)
    x = x + h
    x = maybe_constrain(x, act_spec(tun))
    return x, new_kv, aux


def embed_input(params, cfg, batch):
    """tokens (+ optional patch embeddings) -> (x, positions, prefix_len)."""
    tok = params["embed"][batch["tokens"]]
    if cfg.scale_embed:
        tok = tok * jnp.asarray(cfg.d_model ** 0.5, tok.dtype)
    prefix_len = 0
    if cfg.family == "vlm":
        patches = jnp.einsum("bpd,de->bpe",
                             batch["patches"].astype(tok.dtype),
                             params["patch_proj"])
        x = jnp.concatenate([patches, tok], axis=1)
        prefix_len = cfg.num_patches
    else:
        x = tok
    positions = jnp.arange(x.shape[1])
    return x, positions, prefix_len


def forward(params, cfg, batch, tun, *, return_cache=False):
    """Train / prefill forward. Returns (logits, aux_loss, cache|None)."""
    x, positions, prefix_len = embed_input(params, cfg, batch)
    x = maybe_constrain(x, act_spec(tun))
    n_scan = cfg.n_layers
    aux_total = jnp.zeros((), jnp.float32)
    kv0 = None
    if "layer0" in params:
        x, kv0, aux0 = block_apply(params["layer0"], x, cfg, tun,
                                   positions=positions, window=jnp.int32(0),
                                   prefix_len=prefix_len)
        aux_total += aux0
        n_scan -= 1
    wins = layer_windows(cfg, n_scan)

    def body(carry, xs):
        x, aux = carry
        p_l, win = xs
        x, kv, a = block_apply(p_l, x, cfg, tun, positions=positions,
                               window=win, prefix_len=prefix_len)
        return (x, aux + a), (kv if return_cache else None)

    body = jax.checkpoint(body, policy=REMAT_POLICY[tun.remat])
    (x, aux_total), caches = lax.scan(body, (x, aux_total),
                                      (params["layers"], wins),
                                      unroll=n_scan if tun.layer_unroll else 1)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    head = params.get("head")
    logits = (jnp.einsum("bsd,dv->bsv", x, head) if head is not None
              else jnp.einsum("bsd,vd->bsv", x, params["embed"]))
    logits = L.softcap(logits, cfg.final_softcap)
    logits = maybe_constrain(logits, ("batch", None, "model"))
    cache = None
    if return_cache:
        # (L,B,S,K,hd) -> the sequence-major layout decode reads
        cache = {"k": caches[0].transpose(0, 2, 3, 1, 4),
                 "v": caches[1].transpose(0, 2, 3, 1, 4)}
        if kv0 is not None:
            cache["k0"], cache["v0"] = (a.transpose(1, 2, 0, 3) for a in kv0)
    return logits, aux_total, cache


def decode_step(params, cfg, batch, cache, tun):
    """One-token decode. batch: {"tokens": (B,1), "pos": scalar}.
    cache: {"k": (L,S,K,B,hd), "v": ...}, donated by the caller so the
    token's writes land in place. Returns (logits, new_cache)."""
    pos = batch["pos"]
    tok = params["embed"][batch["tokens"]]
    if cfg.scale_embed:
        tok = tok * jnp.asarray(cfg.d_model ** 0.5, tok.dtype)
    x = tok
    positions = pos[None] if pos.ndim == 0 else pos
    S = cache["k"].shape[1]
    kv_pos = jnp.arange(S)
    kv_len = pos + 1
    n_scan = cfg.n_layers
    new0 = None
    if "layer0" in params:
        x, new0, _ = block_apply(
            params["layer0"], x, cfg, tun, positions=positions,
            window=jnp.int32(0), kv=(cache["k0"], cache["v0"]),
            kv_pos=kv_pos, kv_len=kv_len, write_pos=pos)
        n_scan -= 1
    wins = layer_windows(cfg, n_scan)

    # the stacked cache rides in the carry, so each layer writes only its
    # new token into the donated buffer: no per-step copy of the cache
    def body(carry, xs):
        x, ck, cv = carry
        p_l, win, layer = xs
        x, (ck, cv), _ = block_apply(p_l, x, cfg, tun, positions=positions,
                                     window=win, kv=(ck, cv), kv_pos=kv_pos,
                                     kv_len=kv_len, write_pos=pos,
                                     layer=layer)
        return (x, ck, cv), None

    (x, nk, nv), _ = lax.scan(body, (x, cache["k"], cache["v"]),
                              (params["layers"], wins,
                               jnp.arange(n_scan, dtype=jnp.int32)),
                              unroll=n_scan if tun.layer_unroll else 1)
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    head = params.get("head")
    logits = (jnp.einsum("bsd,dv->bsv", x, head) if head is not None
              else jnp.einsum("bsd,vd->bsv", x, params["embed"]))
    logits = L.softcap(logits, cfg.final_softcap)
    new_cache = dict(cache, k=nk, v=nv)
    if new0 is not None:
        new_cache["k0"], new_cache["v0"] = new0
    return logits, new_cache


def init_cache(cfg, batch: int, seq: int):
    dtype = jnp.dtype(cfg.dtype)
    K, hd = cfg.n_kv_heads, cfg.hd
    n_scan = cfg.n_layers
    cache = {}
    if cfg.moe is not None and cfg.moe.first_layer_dense:
        n_scan -= 1
        cache["k0"] = jnp.zeros((seq, K, batch, hd), dtype)
        cache["v0"] = jnp.zeros((seq, K, batch, hd), dtype)
    cache["k"] = jnp.zeros((n_scan, seq, K, batch, hd), dtype)
    cache["v"] = jnp.zeros((n_scan, seq, K, batch, hd), dtype)
    return cache
