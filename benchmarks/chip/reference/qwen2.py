"""Plain float32 Qwen2 decoder (arXiv:2407.10671), and its weights.

The forward pass follows the published architecture: token embedding,
then per layer RMSNorm, attention with QKV bias, rotary embedding on
half-split channels (theta from the config) and grouped-query heads,
a residual, RMSNorm, a SwiGLU MLP and a residual; a final RMSNorm and the
head tied to the embedding.  Everything is in float32 at the highest
matmul precision, one causal pass over whole sequences, with no cache, no
kernels and no batching tricks.

``make_params`` draws the weights from a key, in the tree layout the
served program takes and in the type it serves (``p["dtype"]``), in one
jitted call.  Norm weights are stored as ``weight - 1``, as the program
stores them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from refmath import F32, einsum, normal, rmsnorm


def _dims(p):
    hd = p.get("head_dim") or p["d_model"] // p["n_heads"]
    return p["d_model"], p["n_heads"], p["n_kv_heads"], hd, p["d_ff"]


def make_params(p: dict, key):
    D, H, K, hd, F = _dims(p)
    L = p["n_layers"]
    Vp = -(-p["vocab"] // 256) * 256
    dt = jnp.dtype(p["dtype"])
    ks = iter(jax.random.split(key, 16))
    layers = {
        "ln1": normal(next(ks), (L, D), 0.1, dt),
        "attn": {
            "wq": normal(next(ks), (L, D, H * hd), D ** -0.5, dt),
            "wk": normal(next(ks), (L, D, K * hd), D ** -0.5, dt),
            "wv": normal(next(ks), (L, D, K * hd), D ** -0.5, dt),
            "wo": normal(next(ks), (L, H * hd, D), (H * hd) ** -0.5, dt),
            "bq": normal(next(ks), (L, H * hd), 0.1, dt),
            "bk": normal(next(ks), (L, K * hd), 0.1, dt),
            "bv": normal(next(ks), (L, K * hd), 0.1, dt),
        },
        "ln2": normal(next(ks), (L, D), 0.1, dt),
        "mlp": {
            "wi": normal(next(ks), (L, D, F), D ** -0.5, dt),
            "wg": normal(next(ks), (L, D, F), D ** -0.5, dt),
            "wo": normal(next(ks), (L, F, D), F ** -0.5, dt),
        },
    }
    return {"embed": normal(next(ks), (Vp, D), 0.02, dt),
            "ln_f": normal(next(ks), (D,), 0.1, dt),
            "layers": layers}


def _rope(x, theta):
    """x: (B, T, heads, hd); rotate the two halves of each head."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def final_hidden(params, p: dict, tokens, precision: str = "float32"):
    """(B, T) tokens -> (B, T, D) float32 hidden states after the final
    norm.  Position t sees positions 0..t only."""
    D, H, K, hd, F = _dims(p)
    eps = p.get("norm_eps", 1e-6)
    B, T = tokens.shape
    x = params["embed"][tokens].astype(F32)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, w):
        h = rmsnorm(x, w["ln1"], eps)
        a = w["attn"]
        q = einsum("btd,de->bte", h, a["wq"], precision) + a["bq"].astype(F32)
        k = einsum("btd,de->bte", h, a["wk"], precision) + a["bk"].astype(F32)
        v = einsum("btd,de->bte", h, a["wv"], precision) + a["bv"].astype(F32)
        q = _rope(q.reshape(B, T, H, hd), p["rope_theta"])
        k = _rope(k.reshape(B, T, K, hd), p["rope_theta"])
        v = v.reshape(B, T, K, hd)
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
        s = einsum("bqhd,bkhd->bhqk", q, k, precision) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        o = einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision)
        x = x + einsum("bte,ed->btd", o.reshape(B, T, H * hd), a["wo"],
                       precision)
        h = rmsnorm(x, w["ln2"], eps)
        m = w["mlp"]
        g = jax.nn.silu(einsum("btd,df->btf", h, m["wg"], precision))
        u = einsum("btd,df->btf", h, m["wi"], precision)
        return x + einsum("btf,fd->btd", g * u, m["wo"], precision), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rmsnorm(x, params["ln_f"], eps)


def logits_at(params, p: dict, tokens, positions, precision="float32"):
    """(B, T) tokens, (B, n) positions -> (B, n, vocab) float32 logits of
    the tied head over the real vocabulary."""
    h = final_hidden(params, p, tokens, precision)
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    return einsum("bnd,vd->bnv", h, params["embed"][:p["vocab"]], precision)
