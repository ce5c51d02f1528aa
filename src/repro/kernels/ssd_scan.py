"""Pallas TPU kernel: Mamba2 SSD chunked scan.

Grid = (batch, head_blocks, chunks); the chunk axis is innermost/sequential,
so the carried SSM state (hb, N, P) of a head block lives in f32 VMEM
scratch across chunk iterations — the inter-chunk recurrence never
round-trips to HBM (on GPU this is the kernel the paper's SSD algorithm
fuses; on TPU the win is identical: the state stays in VMEM and each chunk's
intra-chunk quadratic work feeds the MXU).

Blocking heads bounds VMEM: each program holds (Q, Q) temporaries for one
head at a time instead of (H, Q, Q) for all of them.  The within-chunk
cumulative log-decay is computed by XLA in the wrapper, exactly as the
reference does (Mosaic has no cumsum); everything in the kernel is a 2-D
matmul, an elementwise op or a reduction.

Per chunk (length Q) and head: intra-chunk (C·Bᵀ ⊙ L) x, state read
C·S_prev, state update S = tot·S_prev + Σ decay·dt·B⊗x.

ref oracle: repro.models.mamba2.ssd_chunked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jax.lax.dot(a, b, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)


def _kernel(x_ref, dt_ref, cum_ref, bt_ref, c_ref, y_ref, sfin_ref, s_scr,
            *, nc, hb):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    Bt = bt_ref[0, 0].astype(jnp.float32)   # (N, Q) this head block's group
    Cm = c_ref[0, 0].astype(jnp.float32)    # (Q, N)
    Q = Cm.shape[0]
    CB = _dot(Cm, Bt)                       # (Q, Q): C_i · B_j
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = row >= col
    diag = row == col
    is_last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1

    for h in range(hb):
        x = x_ref[0, h].astype(jnp.float32)             # (Q, P)
        dt = dt_ref[0, pl.ds(h, 1), :]                  # (1, Q)
        cum = cum_ref[0, pl.ds(h, 1), :]                # (1, Q)
        # the same values as a column, via the diagonal (no in-kernel
        # transpose of a single row)
        cum_col = jnp.sum(jnp.where(diag, cum, 0.0), axis=1, keepdims=True)
        last = jnp.sum(jnp.where(is_last, cum, 0.0), axis=1, keepdims=True)

        # intra-chunk: scores[i, j] = (C_i·B_j) exp(cum_i - cum_j) dt_j, i>=j
        # (mask before exp: upper-triangle differences are positive)
        decay = jnp.exp(jnp.where(causal, cum_col - cum, -1e30))
        y = _dot(CB * decay * dt, x)
        # inter-chunk: read the state carried in from earlier chunks
        s_prev = s_scr[h]                               # (N, P)
        y = y + _dot(jnp.exp(cum_col) * Cm, s_prev)
        y_ref[0, h] = y.astype(y_ref.dtype)
        # state update
        w = jnp.exp(last - cum) * dt                    # (1, Q)
        s_scr[h] = s_prev * jnp.exp(last) + _dot(Bt * w, x)

    @pl.when(ci == nc - 1)
    def _done():
        sfin_ref[0] = s_scr[...]


def _head_block(H: int, G: int) -> int:
    """Heads per program: 8 (the sublane tile) when a group holds a
    multiple of 8 heads, else one whole group."""
    r = H // G
    return 8 if r % 8 == 0 else r


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_fwd(x, dt, A, Bm, Cm, *, chunk: int = 256, interpret: bool = False):
    """x: (B,S,H,P), dt: (B,S,H), A: (H,), Bm/Cm: (B,S,G,N).
    Returns (y (B,S,H,P) f32, final_state (B,H,N,P) f32)."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    hb = _head_block(H, G)
    r = H // G

    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum((dt * A).reshape(Bsz, nc, Q, H), axis=2)
    cum = cum.reshape(Bsz, S, H).transpose(0, 2, 1)     # (B,H,S)

    kernel = functools.partial(_kernel, nc=nc, hb=hb)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=(Bsz, H // hb, nc),
        in_specs=[
            pl.BlockSpec((1, hb, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hb, Q), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, hb, Q), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, N, Q), lambda b, h, c: (b, h * hb // r, 0, c)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h * hb // r, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hb, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, N, P), jnp.float32)],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), cum,
      Bm.transpose(0, 2, 3, 1), Cm.transpose(0, 2, 1, 3))
    return y.transpose(0, 2, 1, 3), s_fin


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssd_core(x, dt, A, Bm, Cm, chunk, interpret):
    return _ssd_fwd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


def _ref(x, dt, A, Bm, Cm, chunk):
    from repro.models.mamba2 import ssd_chunked
    y, s = ssd_chunked(x, dt, A, Bm, Cm, chunk)
    return y.astype(jnp.float32), s


def _fwd(x, dt, A, Bm, Cm, chunk, interpret):
    return ssd_core(x, dt, A, Bm, Cm, chunk, interpret), (x, dt, A, Bm, Cm)


def _bwd(chunk, interpret, res, g):
    x, dt, A, Bm, Cm = res
    _, vjp = jax.vjp(lambda *a: _ref(*a, chunk), x, dt, A, Bm, Cm)
    return vjp(g)


ssd_core.defvjp(_fwd, _bwd)


def ssd(x, dt, A, Bm, Cm, *, chunk: int = 256, interpret: bool | None = None):
    """Public entry; ``interpret=None`` asks ``dispatch.interpret_mode``."""
    if interpret is None:
        interpret = dispatch.interpret_mode()
    Q = min(chunk, x.shape[1])
    while x.shape[1] % Q:
        Q //= 2
    return ssd_core(x, dt, A, Bm, Cm, Q, bool(interpret))
