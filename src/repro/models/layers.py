"""Shared model building blocks: norms, RoPE, GQA attention, MLP.

Params are plain dict pytrees. Layer stacks carry a leading ``L`` axis and are
applied with ``lax.scan`` so the lowered HLO stays compact at 512-way SPMD.

Attention has two implementations:
  * ``xla``    — chunked (query-blocked) pure-jnp attention; used for the CPU
                 dry-run lowering and as the Pallas oracle.
  * ``pallas`` — kernels/flash_attention.py (compiled on TPU; see
                 kernels/dispatch.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out, dtype, scale: float | None = None):
    """Normal(0, scale) init; scale defaults to 1/sqrt(d_in)."""
    if scale is None:
        scale = d_in ** -0.5
    shape = (d_in, d_out) if isinstance(d_out, int) else (d_in, *d_out)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv          # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]                              # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# masked GQA attention (chunked XLA path)
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, kv_pos, *, causal: bool, window, prefix_len: int,
               kv_len=None):
    """(Sq, Skv) additive bias in f32. ``window`` may be a traced scalar
    (0 = full attention); ``kv_len`` masks unfilled cache slots."""
    iq = q_pos[:, None]
    jk = kv_pos[None, :]
    ok = jnp.ones(iq.shape[:1] + jk.shape[1:], dtype=bool)
    if causal:
        c = jk <= iq
        if prefix_len:
            c = c | ((iq < prefix_len) & (jk < prefix_len))
        ok = ok & c
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        ok = ok & ((w == 0) | (jk > iq - w))
    if kv_len is not None:
        ok = ok & (jk < kv_len)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _attn_block_impl(q, k, v, bias, softcap: float, scale: float):
    """q: (B,Sq,K,G,D)  k,v: (B,Skv,K,D)  bias: (Sq,Skv)."""
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = s + bias[None, None, None]
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqt,btkd->bqkgd", p, v)


# Never save the O(Sq*Skv) scores/probs for backward — recompute them, which
# is exactly what the Pallas flash kernel does on TPU.
_attn_block_remat = jax.checkpoint(
    _attn_block_impl, policy=jax.checkpoint_policies.nothing_saveable,
    static_argnums=(4, 5))


def _attn_block(q, k, v, bias, *, softcap: float, scale: float):
    return _attn_block_remat(q, k, v, bias, softcap, scale)


def attention_xla(q, k, v, *, q_pos, kv_pos, causal=True, window=None,
                  prefix_len=0, softcap=0.0, kv_len=None, q_chunk=1024,
                  unroll=False):
    """Chunked GQA attention.

    q: (B,Sq,H,D); k,v: (B,Skv,K,D); H % K == 0. Returns (B,Sq,H,D).
    ``unroll`` unrolls the query-chunk loop (dry-run cost-probe accuracy).
    """
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = D ** -0.5
    qg = q.reshape(B, Sq, K, G, D)

    if Sq <= q_chunk:
        bias = _mask_bias(q_pos, kv_pos, causal=causal, window=window,
                          prefix_len=prefix_len, kv_len=kv_len)
        out = _attn_block(qg, k, v, bias, softcap=softcap, scale=scale)
        return out.reshape(B, Sq, H, D)

    assert Sq % q_chunk == 0, (Sq, q_chunk)
    n = Sq // q_chunk
    qc = qg.reshape(B, n, q_chunk, K, G, D).swapaxes(0, 1)   # (n,B,qc,K,G,D)
    pc = q_pos.reshape(n, q_chunk)

    def body(_, xs):
        qi, pi = xs
        bias = _mask_bias(pi, kv_pos, causal=causal, window=window,
                          prefix_len=prefix_len, kv_len=kv_len)
        return None, _attn_block(qi, k, v, bias, softcap=softcap, scale=scale)

    if unroll:
        outs = [body(None, (qc[i], pc[i]))[1] for i in range(n)]
        out = jnp.stack(outs)
    else:
        _, out = lax.scan(body, None, (qc, pc))
    return out.swapaxes(0, 1).reshape(B, Sq, H, D)


# ---------------------------------------------------------------------------
# attention block (projection + rope + attention)
# ---------------------------------------------------------------------------


def attn_init(key, cfg, dtype):
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * hd, dtype),
        "wk": dense_init(ks[1], D, K * hd, dtype),
        "wv": dense_init(ks[2], D, K * hd, dtype),
        "wo": dense_init(ks[3], H * hd, D, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def attn_qkv(p, x, cfg, positions):
    """Project + rope; returns q (B,S,H,hd), k, v (B,S,K,hd)."""
    B, S, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(p, x, cfg, *, positions, causal=True, window=None,
               prefix_len=0, kv=None, kv_pos=None, kv_len=None,
               q_chunk=1024, impl="xla", unroll=False):
    """Full attention block. ``kv``: optional external (k, v) (cross-attn or
    cache); otherwise self-attention over x."""
    B, S, _ = x.shape
    q, k, v = attn_qkv(p, x, cfg, positions)
    if kv is not None:
        k, v = kv
    if kv_pos is None:
        kv_pos = positions if kv is None else jnp.arange(k.shape[1])
    if impl == "pallas":
        from repro.kernels import flash_attention as fa
        out = fa.flash_attention(q, k, v, causal=causal, window=window,
                                 softcap=cfg.attn_softcap, q_pos=positions,
                                 kv_pos=kv_pos)
    else:
        out = attention_xla(q, k, v, q_pos=positions, kv_pos=kv_pos,
                            causal=causal, window=window, prefix_len=prefix_len,
                            softcap=cfg.attn_softcap, kv_len=kv_len,
                            q_chunk=q_chunk, unroll=unroll)
    out = out.reshape(B, S, cfg.n_heads * cfg.hd)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), (k, v)


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------


def mlp_init(key, d: int, f: int, dtype):
    ks = jax.random.split(key, 3)
    return {
        "wi": dense_init(ks[0], d, f, dtype),
        "wg": dense_init(ks[1], d, f, dtype),
        "wo": dense_init(ks[2], f, d, dtype),
    }


def mlp_apply(p, x):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["wg"]))
    h = h * jnp.einsum("bsd,df->bsf", x, p["wi"])
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


def softcap(x, cap: float):
    return cap * jnp.tanh(x / cap) if cap else x
