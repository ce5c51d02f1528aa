"""Mamba2 (SSD — state-space duality) mixer, chunked-scan formulation.

Training/prefill uses the chunked SSD algorithm (intra-chunk quadratic matmuls
+ inter-chunk state recurrence via lax.scan); decode uses the O(1) recurrent
state update. The chunk computation has a Pallas TPU kernel
(kernels/ssd_scan.py); this module is the XLA path and the kernel's oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L


def _dims(cfg):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    H = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, H, conv_dim


def mamba2_init(key, cfg, dtype):
    s = cfg.ssm
    D = cfg.d_model
    d_inner, H, conv_dim = _dims(cfg)
    ks = jax.random.split(key, 5)
    return {
        "in_proj": L.dense_init(ks[0], D, 2 * d_inner + 2 * s.n_groups * s.d_state + H, dtype),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, 1, conv_dim)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": jnp.zeros((d_inner,), dtype),
        "out_proj": L.dense_init(ks[2], d_inner, D, dtype),
    }


# ---------------------------------------------------------------------------
# chunked SSD (XLA reference path)
# ---------------------------------------------------------------------------


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int):
    """x: (B,S,H,P) dt: (B,S,H) A: (H,) Bm/Cm: (B,S,G,N) -> y (B,S,H,P), final state."""
    Bs, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    r = H // G
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    f32 = jnp.float32

    xb = x.reshape(Bs, nc, chunk, H, P).astype(f32)
    dtb = dt.reshape(Bs, nc, chunk, H).astype(f32)
    Bb = Bm.reshape(Bs, nc, chunk, G, N).astype(f32)
    Cb = Cm.reshape(Bs, nc, chunk, G, N).astype(f32)

    a = dtb * A                                             # (B,nc,Q,H), negative
    cum = jnp.cumsum(a, axis=2)
    cum_h = cum.transpose(0, 1, 3, 2)                       # (B,nc,H,Q)

    # --- intra-chunk (quadratic within chunk) ---
    CB = jnp.einsum("bcigN,bcjgN->bcgij", Cb, Bb)           # (B,nc,G,Q,Q)
    CB = jnp.repeat(CB, r, axis=2)                          # (B,nc,H,Q,Q)
    diff = cum_h[..., :, None] - cum_h[..., None, :]
    tril = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask BEFORE exp: upper-triangle diffs are positive and overflow to inf,
    # and where(mask, inf, 0) produces NaN gradients (0 * inf)
    Lmat = jnp.exp(jnp.where(tril, diff, -1e30))
    scores = CB * Lmat * dtb.transpose(0, 1, 3, 2)[..., None, :]
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", scores, xb)

    # --- per-chunk end states ---
    dec_end = jnp.exp(cum_h[..., -1:] - cum_h)              # (B,nc,H,Q)
    Bh = jnp.repeat(Bb, r, axis=3).transpose(0, 1, 2, 3, 4) # (B,nc,Q,H*,N)? see below
    Bh = jnp.repeat(Bb[:, :, :, :, None, :], r, axis=4).reshape(Bs, nc, chunk, H, N)
    w = dec_end.transpose(0, 1, 3, 2) * dtb                 # (B,nc,Q,H)
    S_c = jnp.einsum("bcjh,bcjhn,bcjhp->bchnp", w, Bh, xb)  # (B,nc,H,N,P)

    # --- inter-chunk recurrence ---
    tot = jnp.exp(cum_h[..., -1])                           # (B,nc,H)

    def body(S_prev, inp):
        S_ci, tot_i = inp
        return S_prev * tot_i[..., None, None] + S_ci, S_prev

    init = jnp.zeros((Bs, H, N, P), f32)
    S_last, S_prevs = lax.scan(body, init, (S_c.swapaxes(0, 1), tot.swapaxes(0, 1)))
    S_prevs = S_prevs.swapaxes(0, 1)                        # (B,nc,H,N,P), state before chunk

    Ch = jnp.repeat(Cb[:, :, :, :, None, :], r, axis=4).reshape(Bs, nc, chunk, H, N)
    dec_start = jnp.exp(cum)                                # (B,nc,Q,H)
    y_inter = jnp.einsum("bcih,bcihn,bchnp->bcihp", dec_start, Ch, S_prevs)

    y = (y_intra + y_inter).reshape(Bs, S, H, P)
    return y.astype(x.dtype), S_last


def ssd_step(state, x, dt, A, Bm, Cm):
    """Single-token recurrence. state: (B,H,N,P); x: (B,H,P); dt: (B,H);
    Bm/Cm: (B,G,N)."""
    H = x.shape[1]
    G = Bm.shape[1]
    r = H // G
    f32 = jnp.float32
    x, dt, Bm, Cm = (t.astype(f32) for t in (x, dt, Bm, Cm))
    Bh = jnp.repeat(Bm[:, :, None, :], r, axis=2).reshape(x.shape[0], H, -1)
    Ch = jnp.repeat(Cm[:, :, None, :], r, axis=2).reshape(x.shape[0], H, -1)
    decay = jnp.exp(dt * A)                                  # (B,H)
    upd = jnp.einsum("bh,bhn,bhp->bhnp", dt, Bh, x)
    state = state * decay[..., None, None] + upd
    y = jnp.einsum("bhn,bhnp->bhp", Ch, state)
    return state, y


# ---------------------------------------------------------------------------
# full mixer block
# ---------------------------------------------------------------------------


def _conv_full(xBC, w, b):
    """Causal depthwise conv over time. xBC: (B,S,Cd); w: (k,1,Cd)."""
    k = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (k - 1, 0), (0, 0)))
    out = lax.conv_general_dilated(
        pad, w, window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=xBC.shape[-1])
    return jax.nn.silu(out + b)


def mamba2_apply(p, x, cfg, *, chunk: int | None = None, impl: str = "xla"):
    """Train/prefill path. x: (B,S,D) -> (y, final_state)."""
    s = cfg.ssm
    B, S, D = x.shape
    d_inner, H, conv_dim = _dims(cfg)
    G, N, P = s.n_groups, s.d_state, s.head_dim
    chunk = min(chunk or s.chunk, S)
    while S % chunk:
        chunk //= 2

    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
    conv_tail = xBC[:, S - (s.d_conv - 1):, :]      # raw pre-conv, for decode
    xBC = _conv_full(xBC, p["conv_w"], p["conv_b"])
    xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + G * N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    Bm = Bm.reshape(B, S, G, N)
    Cm = Cm.reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    if impl == "pallas":
        from repro.kernels import ssd_scan as K
        y, S_last = K.ssd(xs, dt, A, Bm, Cm, chunk=chunk)
        y = y.astype(xs.dtype)          # f32 kernel output, as ssd_chunked
    else:
        y, S_last = ssd_chunked(xs, dt, A, Bm, Cm, chunk)
    y = y + (p["D_skip"] * xs.astype(jnp.float32).transpose(0, 1, 3, 2)).transpose(0, 1, 3, 2).astype(y.dtype)

    y = y.reshape(B, S, d_inner)
    y = L.rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, {"ssm": S_last, "conv": conv_tail}


def mamba2_step(p, x, cfg, state):
    """Decode path. x: (B,1,D); state: {"ssm": (B,H,N,P), "conv": (B,k-1,Cd)}."""
    s = cfg.ssm
    B = x.shape[0]
    d_inner, H, conv_dim = _dims(cfg)
    G, N, P = s.n_groups, s.d_state, s.head_dim

    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])[:, 0]
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)

    hist = jnp.concatenate([state["conv"], xBC[:, None, :]], axis=1)  # (B,k,Cd)
    w = p["conv_w"][:, 0, :]                                          # (k,Cd)
    xBC = jax.nn.silu(jnp.einsum("bkc,kc->bc", hist, w) + p["conv_b"])
    new_conv = hist[:, 1:]

    xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    new_ssm, y = ssd_step(state["ssm"], xs.reshape(B, H, P), dt,
                          A, Bm.reshape(B, G, N), Cm.reshape(B, G, N))
    y = y + p["D_skip"][:, None] * xs.reshape(B, H, P).astype(jnp.float32)
    y = y.reshape(B, 1, d_inner).astype(x.dtype)
    y = L.rmsnorm(y * jax.nn.silu(z[:, None]), p["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, {"ssm": new_ssm, "conv": new_conv}


def mamba2_init_state(cfg, batch: int, dtype):
    s = cfg.ssm
    d_inner, H, conv_dim = _dims(cfg)
    return {
        "ssm": jnp.zeros((batch, H, s.d_state, s.head_dim), jnp.float32),
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
    }
