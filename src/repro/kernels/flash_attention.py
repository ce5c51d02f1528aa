"""Pallas TPU flash attention (GQA, causal/sliding-window, logit softcap).

Blocked online-softmax over KV tiles. Grid = (batch, q_head, q_blocks,
kv_blocks); the kv_blocks axis is innermost and sequential on TPU, so the
running max/denominator/accumulator live in VMEM scratch that persists across
kv iterations of the same output block; the output tile is written on the
last kv block. BlockSpecs keep one (bq, d) query tile and one (bk, d) KV tile
resident — MXU-aligned for d = 128-multiples.

The backward pass deliberately recomputes through the XLA reference
(jax.custom_vjp): identical math, and the paper's training path already
treats attention internals as recompute-not-save (DESIGN.md §6).

ref oracle: repro.models.layers.attention_xla.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq, bk, n_kv_blocks, causal, window, softcap, scale, kv_len):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)             # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)             # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok = ok & (k_pos <= q_pos)
    if window is not None and window > 0:
        ok = ok & (k_pos > q_pos - window)
    if kv_len is not None:
        ok = ok & (k_pos < kv_len)
    s = jnp.where(ok, s, NEG)

    m_prev = m_scr[...]                              # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(kb == n_kv_blocks - 1)
    def _done():
        o_ref[0, 0] = (acc_scr[...] /
                         jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "bq", "bk", "interpret"))
def _flash_fwd(q, k, v, kv_len=None, *, causal=True, window=0, softcap=0.0,
               bq=128, bk=128, interpret=False):
    """q: (B, Sq, H, d); k,v: (B, Skv, K, d) -> (B, Sq, H, d)."""
    B, Sq, H, d = q.shape
    K = k.shape[2]
    G = H // K
    scale = d ** -0.5

    bq = min(bq, Sq)
    bk = min(bk, k.shape[1])
    qpad = (-Sq) % bq
    kpad = (-k.shape[1]) % bk
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0)))
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        if kv_len is None:
            kv_len = k.shape[1] - kpad
    Sqp, Skvp = q.shape[1], k.shape[1]
    nq, nk = Sqp // bq, Skvp // bk

    # (B, H, S, d) layout for clean blocking
    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, n_kv_blocks=nk, causal=causal,
        window=window, softcap=softcap, scale=scale, kv_len=kv_len)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            # GQA: query head h reads kv head h // G
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qT, kT, vT)
    return out.transpose(0, 2, 1, 3)[:, :Sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_core(q, k, v, causal, window, softcap, interpret):
    return _flash_fwd(q, k, v, causal=causal, window=window,
                      softcap=softcap, interpret=interpret)


def _ref(q, k, v, causal, window, softcap):
    from repro.models.layers import attention_xla
    return attention_xla(q, k, v, q_pos=jnp.arange(q.shape[1]),
                         kv_pos=jnp.arange(k.shape[1]), causal=causal,
                         window=window if window else None, softcap=softcap,
                         q_chunk=max(q.shape[1], 1))


def _fwd(q, k, v, causal, window, softcap, interpret):
    return flash_attention_core(q, k, v, causal, window, softcap,
                                interpret), (q, k, v)


def _bwd(causal, window, softcap, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda a, b, c: _ref(a, b, c, causal, window, softcap),
                     q, k, v)
    return vjp(g)


flash_attention_core.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=0.0,
                    q_pos=None, kv_pos=None, interpret=None):
    """Public entry.  ``interpret=None`` asks ``dispatch.interpret_mode``:
    compiled on a TPU, an error elsewhere unless interpretation is asked
    for."""
    if interpret is None:
        interpret = dispatch.interpret_mode()
    w = int(window) if window is not None and not hasattr(window, "shape") \
        else 0
    return flash_attention_core(q, k, v, causal, w, float(softcap),
                                bool(interpret))
