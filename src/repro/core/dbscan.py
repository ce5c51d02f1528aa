"""DBSCAN workload discovery in JAX (Algorithm 2, discovery step).

Two execution paths share one semantics:

* **fast** (default) — the streaming path.  ``kernels.pairdist.
  neighbor_adjacency`` produces per-row ε-neighbour counts and a bit-packed
  adjacency matrix without materializing (N, N) float32; cluster labels then
  converge by min-label propagation with **pointer jumping** (every sweep
  also applies ``lab = min(lab, lab[lab])`` path compression to a fixed
  point), so the number of O(N²/8) neighbour sweeps is O(log N) instead of
  O(cluster diameter).  Scales to N ≈ 8–16k windows.
* **legacy / ref** — the seed formulation: dense (N, N) distance matrix and
  one-hop-per-iteration propagation.  Kept as the parity oracle
  (``impl="ref"``) and for benchmarking the seed path (``impl="legacy"``).

Both yield bit-identical labels: core points take the minimum index of their
core-connected component, border points adopt the smallest core-neighbour
label, noise is -1, and clusters are renumbered 0..k-1 in root order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch
from repro.kernels.pairdist import neighbor_adjacency, unpack_bits


def pairwise_sq_dists(x, impl: str = "auto"):
    """Dense (N, N) squared distances.  Legacy entry point — the fast path
    never calls this; kept for the oracle and the seed benchmark mode."""
    if impl in ("pallas", "pallas_interpret", "legacy"):
        from repro.kernels import pairdist
        want = "pallas_interpret" if impl == "legacy" else impl
        return pairdist.pairdist(x, interpret=dispatch.interpret_mode(want))
    x = x.astype(jnp.float32)
    n2 = jnp.sum(x * x, axis=1)
    d2 = n2[:, None] + n2[None, :] - 2.0 * (x @ x.T)
    return jnp.maximum(d2, 0.0)


# -- seed formulation (oracle) ------------------------------------------------


@jax.jit
def _dbscan_core(d2, eps_sq, min_pts):
    """One-hop min-label propagation over the dense adjacency matrix.
    O(diameter) sweeps of O(N²) — the seed implementation and the oracle the
    fast path is tested against."""
    n = d2.shape[0]
    adj = d2 <= eps_sq                                    # ε-neighbourhood
    n_nbr = jnp.sum(adj, axis=1)                          # includes self
    core = n_nbr >= min_pts

    cc = adj & core[:, None] & core[None, :]              # core-core edges
    cc = cc | jnp.eye(n, dtype=bool)
    labels0 = jnp.where(core, jnp.arange(n), n)           # n = +inf sentinel

    def body(state):
        lab, _ = state
        # min label over core neighbours
        nbr_min = jnp.min(jnp.where(cc, lab[None, :], n), axis=1)
        new = jnp.minimum(lab, nbr_min)
        return new, jnp.any(new != lab)

    def cond(state):
        return state[1]

    labels, _ = jax.lax.while_loop(cond, body, (labels0, jnp.bool_(True)))

    # border points: adopt min core-neighbour label
    border_adj = adj & core[None, :]
    border_lab = jnp.min(jnp.where(border_adj, labels[None, :], n), axis=1)
    labels = jnp.where(core, labels, jnp.where(border_lab < n, border_lab, -1))
    return labels


# -- streaming fast path ------------------------------------------------------


def _min_core_neighbor(lab_ext, packed, bm: int):
    """Per-row min of ``lab_ext`` over set adjacency bits, one (bm, N) strip
    at a time (lab_ext carries the sentinel Np at non-core columns)."""
    np_, w = packed.shape

    def strip(pb):                                        # (bm, W) uint8
        bits = unpack_bits(pb)                            # (bm, Np) bool
        return jnp.min(jnp.where(bits, lab_ext[None, :], np_), axis=1)

    return jax.lax.map(strip, packed.reshape(np_ // bm, bm, w)).reshape(np_)


@functools.partial(jax.jit, static_argnames=("block",))
def _dbscan_core_packed(counts, packed, min_pts, n, block: int):
    """DBSCAN labels from the fused neighbour kernel's outputs.

    Pointer-jumping propagation: each sweep takes the min core-neighbour
    label (one pass over the packed adjacency) and then compresses label
    chains to a fixed point with ``lab = min(lab, lab[lab])``, which at
    least halves every chain — O(log N) sweeps to converge on any graph.
    """
    np_ = packed.shape[0]
    bm = min(block, np_)
    rows = jnp.arange(np_, dtype=jnp.int32)
    core = (counts >= min_pts) & (rows < n)               # padding: never core

    def compress(lab):
        def body(state):
            l, _ = state
            l2 = jnp.minimum(l, l[l])
            return l2, jnp.any(l2 != l)

        lab, _ = jax.lax.while_loop(lambda s: s[1], body,
                                    (lab, jnp.bool_(True)))
        return lab

    def sweep(state):
        lab, _ = state
        lab_ext = jnp.where(core, lab, np_)
        nbr = _min_core_neighbor(lab_ext, packed, bm)
        new = jnp.where(core, jnp.minimum(lab, nbr.astype(jnp.int32)), lab)
        new = compress(new)
        return new, jnp.any(new != lab)

    labels, _ = jax.lax.while_loop(lambda s: s[1], sweep,
                                   (rows, jnp.bool_(True)))

    # border points adopt the min core-neighbour label; the rest is noise
    lab_ext = jnp.where(core, labels, np_)
    border = _min_core_neighbor(lab_ext, packed, bm)
    return jnp.where(core, labels,
                     jnp.where(border < np_, border, -1))


def _relabel(raw: np.ndarray) -> np.ndarray:
    """Renumber cluster roots to 0..k-1 (ascending root order), noise = -1."""
    uniq, inv = np.unique(raw, return_inverse=True)
    out = inv.astype(np.int64)
    if uniq.size and uniq[0] < 0:
        out -= 1
    return out


def dbscan(x, eps: float, min_pts: int = 5, impl: str = "auto",
           block: int = 128) -> np.ndarray:
    """x: (N, F) -> labels (N,) int, noise = -1, clusters renumbered 0..k-1.

    ``impl``: "auto" picks the streaming compiled path for the current
    backend (see kernels/dispatch.py); "ref" is the dense one-hop oracle;
    "legacy" is the seed path (dense interpret-mode Pallas matrix).
    """
    x = jnp.asarray(x)
    n = x.shape[0]
    if n == 0:
        return np.zeros(0, np.int64)
    block = max(8, block - block % 8)   # match the kernel's bit-pack rounding
    if impl in ("ref", "legacy"):
        d2 = pairwise_sq_dists(x, "auto" if impl == "ref" else "legacy")
        raw = np.asarray(_dbscan_core(d2, jnp.float32(eps * eps),
                                      jnp.int32(min_pts)))
    else:
        counts, packed = neighbor_adjacency(x, eps, block=block, impl=impl)
        raw = np.asarray(_dbscan_core_packed(
            counts, packed, jnp.int32(min_pts), jnp.int32(n),
            block=block)[:n])
    return _relabel(raw)


def kmeans(x, k: int, iters: int = 50, seed: int = 0) -> np.ndarray:
    """Baseline clusterer for the Fig-10 comparison."""
    x = jnp.asarray(x, jnp.float32)
    key = jax.random.PRNGKey(seed)
    idx = jax.random.choice(key, x.shape[0], (k,), replace=False)
    cent = x[idx]

    def step(cent, _):
        d2 = jnp.sum((x[:, None] - cent[None]) ** 2, -1)
        a = jnp.argmin(d2, 1)
        oh = jax.nn.one_hot(a, k, dtype=jnp.float32)
        tot = oh.T @ x
        cnt = oh.sum(0)[:, None]
        new = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1), cent)
        return new, None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    d2 = jnp.sum((x[:, None] - cent[None]) ** 2, -1)
    return np.asarray(jnp.argmin(d2, 1))


def agglomerative_single_link(x, dist_thresh: float,
                              impl: str = "auto") -> np.ndarray:
    """Single-linkage connected components at a distance threshold — the
    third clusterer in the Fig-10 comparison (threshold-graph variant).

    Connected components of the ε-threshold graph are exactly DBSCAN with
    ``min_pts=1`` (every point is core, there is no noise), so this rides
    the same streaming pointer-jumping path instead of the seed's
    O(N² · diameter) numpy loop.
    """
    return dbscan(x, eps=float(dist_thresh), min_pts=1, impl=impl)
