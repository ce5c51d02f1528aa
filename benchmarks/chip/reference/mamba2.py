"""Plain float32 Mamba-2 language model (arXiv:2405.21060), and its weights.

The forward pass follows the published block: RMSNorm, one input
projection into the gate ``z``, the convolved channels ``xBC`` and the step
``dt``; a causal depthwise convolution with bias and SiLU over ``xBC``;
the selective state-space recurrence per head, token by token,

    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t x_t^T,   y_t = C_t h_t + D x_t,

with ``dt_t = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``; the gated
RMSNorm ``norm(y * silu(z))``; the output projection and a residual.  A
final RMSNorm and the head tied to the embedding close it.  Everything is
in float32 at the highest matmul precision, a sequential scan over time,
with none of the chunked algorithm the program runs.

``make_params`` draws the weights in the served program's tree layout and
type, in one jitted call; ``A_log``, ``D`` and ``dt_bias`` stay float32 as
the program keeps them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from refmath import F32, einsum, normal, rmsnorm


def _dims(p):
    s = p["ssm"]
    d_inner = s["expand"] * p["d_model"]
    H = d_inner // s["head_dim"]
    conv_dim = d_inner + 2 * s["n_groups"] * s["d_state"]
    return s, d_inner, H, conv_dim


def make_params(p: dict, key):
    s, d_inner, H, conv_dim = _dims(p)
    D, L = p["d_model"], p["n_layers"]
    G, N = s["n_groups"], s["d_state"]
    Vp = -(-p["vocab"] // 256) * 256
    dt = jnp.dtype(p["dtype"])
    ks = iter(jax.random.split(key, 16))
    # dt spread log-uniformly over [1e-3, 1e-1] and A over [1, 16], as the
    # published initialisation draws them
    step = jnp.exp(jax.random.uniform(next(ks), (L, H), F32,
                                      jnp.log(1e-3), jnp.log(1e-1)))
    mixer = {
        "in_proj": normal(next(ks), (L, D, 2 * d_inner + 2 * G * N + H),
                          D ** -0.5, dt),
        "conv_w": normal(next(ks), (L, s["d_conv"], 1, conv_dim), 0.3, dt),
        "conv_b": normal(next(ks), (L, conv_dim), 0.1, dt),
        "A_log": jnp.log(jax.random.uniform(next(ks), (L, H), F32, 1.0, 16.0)),
        "D_skip": 1.0 + normal(next(ks), (L, H), 0.1, F32),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "norm": normal(next(ks), (L, d_inner), 0.1, dt),
        "out_proj": normal(next(ks), (L, d_inner, D), d_inner ** -0.5, dt),
    }
    return {"embed": normal(next(ks), (Vp, D), 0.02, dt),
            "layers": {"ln": normal(next(ks), (L, D), 0.1, dt),
                       "mixer": mixer},
            "ln_f": normal(next(ks), (D,), 0.1, dt)}


def _mixer(w, h, p, precision):
    s, d_inner, H, conv_dim = _dims(p)
    G, N, P, k = s["n_groups"], s["d_state"], s["head_dim"], s["d_conv"]
    B, T, _ = h.shape
    eps = p.get("norm_eps", 1e-6)
    zxbcdt = einsum("btd,de->bte", h, w["in_proj"], precision)
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt = zxbcdt[..., d_inner + conv_dim:]
    # causal depthwise convolution: output t mixes inputs t-k+1 .. t
    cw = w["conv_w"][:, 0, :].astype(F32)                        # (k, C)
    padded = jnp.pad(xBC, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + T, :] * cw[i] for i in range(k))
    xBC = jax.nn.silu(conv + w["conv_b"].astype(F32))
    x = xBC[..., :d_inner].reshape(B, T, H, P)
    Bm = xBC[..., d_inner:d_inner + G * N].reshape(B, T, G, N)
    Cm = xBC[..., d_inner + G * N:].reshape(B, T, G, N)
    Bm = jnp.repeat(Bm, H // G, axis=2)                          # (B,T,H,N)
    Cm = jnp.repeat(Cm, H // G, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"])                      # (B,T,H)
    A = -jnp.exp(w["A_log"])                                     # (H,)

    def step(state, xs):
        x_t, B_t, C_t, dt_t = xs                  # (B,H,P) (B,H,N) (B,H,N) (B,H)
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + dt_t[..., None, None] * B_t[..., :, None] * x_t[..., None, :]
        y = jnp.einsum("bhn,bhnp->bhp", C_t, state,
                       precision=jax.lax.Precision.HIGHEST)
        return state, y

    state0 = jnp.zeros((B, H, N, P), F32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt))
    _, y = jax.lax.scan(step, state0, xs)
    y = jnp.moveaxis(y, 0, 1) + w["D_skip"][:, None] * x         # (B,T,H,P)
    y = y.reshape(B, T, d_inner) * jax.nn.silu(z)
    y = rmsnorm(y, w["norm"], eps)
    return einsum("bte,ed->btd", y, w["out_proj"], precision)


def final_hidden(params, p: dict, tokens, precision: str = "float32"):
    """(B, T) tokens -> (B, T, D) float32 hidden states after the final
    norm.  Position t sees positions 0..t only."""
    eps = p.get("norm_eps", 1e-6)
    x = params["embed"][tokens].astype(F32)

    def layer(x, w):
        return x + _mixer(w["mixer"], rmsnorm(x, w["ln"], eps), p,
                          precision), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rmsnorm(x, params["ln_f"], eps)


def logits_at(params, p: dict, tokens, positions, precision="float32"):
    """(B, T) tokens, (B, n) positions -> (B, n, vocab) float32 logits of
    the tied head over the real vocabulary."""
    h = final_hidden(params, p, tokens, precision)
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    return einsum("bnd,vd->bnv", h, params["embed"][:p["vocab"]], precision)
