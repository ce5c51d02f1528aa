"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

For cross-pod scaling beyond what DP over 'pod' gives, layer stacks can be
partitioned into S stages and microbatched: stage s processes microbatch
m = t - s at tick t, activations hop stages via ppermute, and every stage
computes every tick (inactive ticks are masked — the standard SPMD-gpipe
trade: (S-1) bubble ticks of wasted compute for a single collective-permute
per tick of point-to-point traffic, which is what the slow DCN axis wants).

``gpipe_apply`` is family-agnostic: it takes the per-stage stacked params and
a ``stage_fn(stage_params, x)`` (e.g. a lax.scan over that stage's layers).
Correctness is validated against the sequential stack in
tests/test_pipeline.py on an 8-device host platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def stage_split(params_stacked, n_stages: int):
    """Reshape stacked layer params (L, ...) -> (S, L/S, ...)."""
    def f(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])
    return jax.tree_util.tree_map(f, params_stacked)


def gpipe_apply(params_staged, x, stage_fn, *, mesh: Mesh,
                n_microbatches: int, axis: str = "stage"):
    """x: (B, ...) -> (B, ...) after all stages, pipelined.

    params_staged: pytree with leading (S, L/S, ...) axes (see stage_split).
    stage_fn(stage_params, x_mb) applies one stage to one microbatch.
    """
    S = mesh.shape[axis]
    M = n_microbatches
    B = x.shape[0]
    assert B % M == 0, (B, M)
    xs = x.reshape(M, B // M, *x.shape[1:])

    def shard_fn(p_local, xs):
        # p_local: (1, L/S, ...) this stage's params; xs: (M, mb, ...) full
        p_local = jax.tree_util.tree_map(lambda a: a[0], p_local)
        s = lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        carry = jnp.zeros(mb_shape, xs.dtype)      # inbound activation buffer
        out = jnp.zeros_like(xs)                   # collected at last stage
        perm = [(i, (i + 1) % S) for i in range(S)]
        for t in range(M + S - 1):
            m = t - s                               # microbatch index here
            inp = jnp.where(s == 0,
                            xs[jnp.clip(t, 0, M - 1)],
                            carry)
            y = stage_fn(p_local, inp)
            active = (m >= 0) & (m < M)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage collects its finished microbatch
            is_last = s == S - 1
            out = lax.dynamic_update_index_in_dim(
                out,
                jnp.where(active & is_last, y, out[jnp.clip(m, 0, M - 1)]),
                jnp.clip(m, 0, M - 1), 0)
            carry = lax.ppermute(y, axis, perm)
        # stack per-stage; only the last stage's slice is meaningful
        return out[None]

    pspec = jax.tree_util.tree_map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), params_staged)
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(pspec, P()),
                       out_specs=P(axis))
    out = fn(params_staged, xs)[-1]
    return out.reshape(B, *x.shape[1:])
