"""Arithmetic shared by the plain references: matmuls in float32 at the
highest precision, or, for the control, with both operands rounded to
float8 (e4m3, one scale per row of each operand) and accumulated in
float32, which is how an fp8 serving path computes."""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "float8")
_E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` to e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def einsum(spec: str, a, b, precision: str):
    """``jnp.einsum`` over two float32 operands; ``float8`` rounds each
    operand along its contracted axis first."""
    a, b = a.astype(F32), b.astype(F32)
    if precision == "float8":
        ins, _ = spec.split("->")
        sa, sb = ins.split(",")
        contracted = set(sa) & set(sb) - set(spec.split("->")[1])
        a = _fp8(a, tuple(i for i, c in enumerate(sa) if c in contracted))
        b = _fp8(b, tuple(i for i, c in enumerate(sb) if c in contracted))
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, weight, eps):
    """RMSNorm with the published weight (the program stores weight - 1)."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + weight.astype(F32))


def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)
