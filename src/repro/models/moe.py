"""Mixture-of-Experts FFN: shared experts + routed top-k with capacity dispatch.

Two dispatch paths:

* **single-device** (smoke tests): scatter tokens into an (E, C, d) buffer via
  cumsum positions, batched expert einsums, gather back.
* **expert-parallel shard_map** (any active mesh): the expert axis E lives on
  'model' (EP) and tokens on 'data'/'pod'. Each (data, model) shard dispatches
  its *local* tokens to its *local* experts — per-device flops are
  global/(dp·tp) with zero dispatch collectives — and partial outputs combine
  with one psum over 'model' (tokens are replicated over 'model' coming in).
  GSPMD cannot infer this from a scatter, so we state it explicitly; this is
  the DeepSpeed-MoE-style a2a-free layout possible because activations enter
  the FFN replicated over the TP axis.

Capacity semantics are standard: per-shard capacity C = cf·T_local·k/E;
overflow tokens are dropped (the residual stream carries them unchanged).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.sharding import rules


def moe_init(key, cfg, dtype):
    m = cfg.moe
    d, fe, e = cfg.d_model, m.d_expert, m.num_experts
    ks = jax.random.split(key, 6)
    p = {
        "router": L.dense_init(ks[0], d, e, dtype, scale=0.02),
        "wi": (jax.random.normal(ks[1], (e, d, fe)) * d ** -0.5).astype(dtype),
        "wg": (jax.random.normal(ks[2], (e, d, fe)) * d ** -0.5).astype(dtype),
        "wo": (jax.random.normal(ks[3], (e, fe, d)) * fe ** -0.5).astype(dtype),
    }
    if m.num_shared:
        p["shared"] = L.mlp_init(ks[4], d, m.num_shared * fe, dtype)
    if m.dense_ff:
        p["dense"] = L.mlp_init(ks[5], d, m.dense_ff, dtype)
    return p


def _dispatch_compute(xt, gate, idx, wi, wg, wo, *, num_experts: int,
                      cf: float, e_offset=0):
    """Capacity-dispatch xt's tokens to the local expert slice and compute.

    xt: (T, D); gate/idx: (T, K); wi/wg: (E_l, D, Fe); wo: (E_l, Fe, D).
    ``e_offset``: first global expert id owned here. Returns (T, D) partial
    output (zero rows for tokens routed to non-local/overflowed experts).
    """
    T, D = xt.shape
    K = idx.shape[1]
    E_l = wi.shape[0]
    C = max(int(cf * T * K / num_experts), 1)

    flat_e = idx.reshape(-1) - e_offset                     # (T*K,)
    flat_w = gate.reshape(-1).astype(xt.dtype)
    own = (flat_e >= 0) & (flat_e < E_l)
    oh = jnp.where(own[:, None],
                   jax.nn.one_hot(flat_e, E_l, dtype=jnp.int32), 0)
    pos = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1         # (T*K,)
    keep = own & (pos >= 0) & (pos < C)
    pos_c = jnp.where(keep, pos, 0)
    e_c = jnp.where(keep, flat_e, 0)

    tok = jnp.repeat(xt, K, axis=0)
    tok = jnp.where(keep[:, None], tok, 0)
    buf = jnp.zeros((E_l, C, D), xt.dtype).at[e_c, pos_c].add(tok)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    h = h * jnp.einsum("ecd,edf->ecf", buf, wi)
    out = jnp.einsum("ecf,efd->ecd", h, wo)                 # (E_l, C, D)

    y = out[e_c, pos_c] * (flat_w * keep.astype(flat_w.dtype))[:, None]
    return y.reshape(T, K, D).sum(axis=1)


def moe_apply(p, x, cfg, *, capacity_factor: float | None = None):
    """x: (B, S, D) -> (y, aux_loss)."""
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.num_experts, m.top_k
    cf = capacity_factor if capacity_factor is not None else m.capacity_factor
    T = B * S
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt, p["router"].astype(xt.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                     # (T, K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce) * m.router_aux_weight

    mesh = rules.current_mesh()
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    if mesh is not None and tp > 1 and E % tp == 0:
        batch = rules._resolve(("batch",), mesh)[0]         # 'data'/('pod','data')
        tok_spec = P(batch, None)

        def local(xt_l, gate_l, idx_l, wi_l, wg_l, wo_l):
            j = lax.axis_index("model")
            y = _dispatch_compute(xt_l, gate_l, idx_l, wi_l, wg_l, wo_l,
                                  num_experts=E, cf=cf,
                                  e_offset=j * (E // tp))
            return lax.psum(y, "model")

        y = jax.shard_map(
            local, mesh=mesh,
            in_specs=(tok_spec, tok_spec, tok_spec,
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=tok_spec,
        )(xt, gate.astype(xt.dtype), idx, p["wi"], p["wg"], p["wo"])
    else:
        y = _dispatch_compute(xt, gate, idx, p["wi"], p["wg"], p["wo"],
                              num_experts=E, cf=cf)

    if m.num_shared:
        y = y + L.mlp_apply(p["shared"], xt[None])[0]
    if m.dense_ff:
        y = y + L.mlp_apply(p["dense"], xt[None])[0]
    return y.reshape(B, S, D), aux


def expert_load(p, x, cfg):
    """Telemetry: fraction of tokens landing on the busiest expert (imbalance)."""
    m = cfg.moe
    T = x.shape[0] * x.shape[1]
    logits = jnp.einsum("td,de->te", x.reshape(T, -1), p["router"].astype(x.dtype))
    idx = jnp.argmax(logits, axis=-1)
    counts = jnp.bincount(idx, length=m.num_experts)
    return counts.max() / jnp.maximum(T / m.num_experts, 1.0)
