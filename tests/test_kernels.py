"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode),
plus gradient checks through the custom-vjp wrappers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention
from repro.kernels.pairdist import pairdist
from repro.kernels.ssd_scan import ssd


@pytest.mark.parametrize("n,f,dtype", [
    (64, 8, jnp.float32), (200, 16, jnp.float32), (130, 4, jnp.bfloat16),
])
def test_pairdist_sweep(n, f, dtype, rng_key):
    x = jax.random.normal(rng_key, (n, f)).astype(dtype)
    got = pairdist(x, block=64, interpret=True)
    want = R.ref_pairdist(x)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


ATTN_CASES = [
    # B, Sq, Skv, H, K, d, causal, window, softcap, dtype
    (2, 128, 128, 4, 2, 64, True, 0, 0.0, jnp.float32),
    (1, 256, 256, 8, 1, 32, True, 64, 50.0, jnp.float32),
    (2, 64, 128, 4, 4, 64, False, 0, 0.0, jnp.float32),
    (1, 96, 96, 2, 2, 128, True, 0, 30.0, jnp.float32),
    (2, 128, 128, 4, 2, 64, True, 0, 0.0, jnp.bfloat16),
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_sweep(case, rng_key):
    B, Sq, Skv, H, K, d, causal, win, cap, dtype = case
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, d)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, K, d)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, K, d)).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, window=win or None,
                          softcap=cap, interpret=True)
    want = R.attention_ref(q, k, v, causal=causal, window=win or None,
                           softcap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_grad_matches_ref(rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))

    def f_kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    def f_ref(q, k, v):
        return R.attention_ref(q, k, v, causal=True).sum()

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


SSD_CASES = [
    (2, 128, 4, 16, 1, 32, 32, jnp.float32),
    (1, 256, 8, 32, 2, 16, 64, jnp.float32),
    (1, 64, 2, 8, 1, 8, 16, jnp.bfloat16),
    # two head blocks of 8 per group, two groups: the grid's head axis
    (1, 64, 32, 8, 2, 8, 16, jnp.float32),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_sweep(case, rng_key):
    B, S, H, P, G, N, chunk, dtype = case
    ks = jax.random.split(rng_key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, S, G, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, G, N)) * 0.3).astype(dtype)
    yk, sk = ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, sr = R.ssd_ref(x.astype(jnp.float32), dt, A,
                       Bm.astype(jnp.float32), Cm.astype(jnp.float32), chunk)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr),
                               rtol=tol, atol=tol)


def test_ssd_grad_runs(rng_key):
    ks = jax.random.split(rng_key, 5)
    B, S, H, P, G, N = 1, 64, 2, 8, 1, 8
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3

    g = jax.grad(lambda x: ssd(x, dt, A, Bm, Cm, chunk=16,
                               interpret=True)[0].sum())(x)
    assert np.all(np.isfinite(np.asarray(g)))
