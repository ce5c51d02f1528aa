"""Pallas TPU kernels: tiled pairwise squared distances and the fused
ε-neighbourhood kernel (per-row neighbour counts + bit-packed adjacency).

This is KERMIT's workload-discovery hot-spot: DBSCAN over the window history
is O(N²F) and reruns at every off-line analysis interval.  Two kernels:

* ``pairdist``            — materializes the (N, N) float32 matrix, tiled
                            into MXU-aligned (bm, bn) blocks.  Kept for the
                            oracle path and small N.
* ``neighbor_adjacency``  — the streaming fast path.  Walks the same (bm, bn)
                            tile grid but never writes the float32 matrix:
                            each tile is thresholded at ε² in registers and
                            reduced to (a) an int32 per-row neighbour-count
                            accumulator and (b) a bit-packed uint8 adjacency
                            block (8 columns per byte), an 8×/32× smaller
                            HBM footprint than bool/float32.

Backend selection lives in ``kernels.dispatch``: compiled Pallas on TPU,
a tiled pure-jnp twin (identical arithmetic, identical packing) on CPU, and
interpret mode only on explicit request.

ref.py oracle: ``ref_pairdist`` below (pure jnp).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import dispatch


def ref_pairdist(x):
    """(N, F) -> (N, N) squared euclidean distances."""
    x = x.astype(jnp.float32)
    n2 = jnp.sum(x * x, axis=1)
    d2 = n2[:, None] + n2[None, :] - 2.0 * (x @ x.T)
    return jnp.maximum(d2, 0.0)


def ref_neighbor_count(x, eps):
    return jnp.sum(ref_pairdist(x) <= eps * eps, axis=1)


def ref_adjacency(x, eps):
    """(N, F) -> (N, N) bool ε-neighbourhood matrix (oracle)."""
    return ref_pairdist(x) <= eps * eps


# -- dense pairdist (oracle / small-N path) -----------------------------------


def _kernel(x_ref, y_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (bm, F)
    y = y_ref[...].astype(jnp.float32)          # (bn, F)
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    yy = jnp.sum(y * y, axis=1, keepdims=True)
    xy = jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    o_ref[...] = jnp.maximum(xx + yy.T - 2.0 * xy, 0.0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def pairdist(x, *, block: int = 128, interpret: bool | None = None):
    """(N, F) -> (N, N) squared distances via pl.pallas_call."""
    if interpret is None:
        interpret = dispatch.interpret_mode()
    n, f = x.shape
    bm = min(block, n)
    npad = (-n) % bm
    if npad:
        x = jnp.pad(x, ((0, npad), (0, 0)))
    np_ = x.shape[0]
    grid = (np_ // bm, np_ // bm)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, f), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, f), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, np_), jnp.float32),
        interpret=interpret,
    )(x, x)
    return out[:n, :n]


# -- fused streaming ε-neighbourhood kernel -----------------------------------
#
# Bit layout: adjacency column j lives in byte j // 8, bit j % 8 (LSB first).
# ``unpack_bits`` is the single source of truth for that layout.  The XLA
# twin packs with ``_pack_bits`` (a reshape and a shift-sum); the Pallas
# kernel packs with a matmul against ``_pack_matrix``, because Mosaic cannot
# split the lane axis in-kernel.  Both give the same integers, so the two
# outputs are bit-identical.

# One lane-dense row of packed bytes (128 lanes) covers 1024 columns; wider
# problems walk column tiles of that size so every packed block is a whole
# row or exactly 128 lanes wide (the TPU's (8, 128) tiling).
_TILE_COLS = 8 * 128


def _bit_positions():
    # built inline (not a module constant) so Pallas kernels don't capture it
    return jax.lax.iota(jnp.int32, 8)


def _pack_bits(adj):
    """(..., K) bool with K % 8 == 0 -> (..., K // 8) uint8."""
    b = adj.reshape(adj.shape[:-1] + (adj.shape[-1] // 8, 8))
    return jnp.sum(b.astype(jnp.int32) << _bit_positions(),
                   axis=-1).astype(jnp.uint8)


def unpack_bits(packed, n_cols: int | None = None):
    """(..., W) uint8 -> (..., 8 * W) bool; optionally trimmed to n_cols."""
    bits = (packed[..., None].astype(jnp.int32) >> _bit_positions()) & 1
    out = bits.reshape(packed.shape[:-1] + (packed.shape[-1] * 8,)) != 0
    return out if n_cols is None else out[..., :n_cols]


def _pack_matrix(bn: int):
    """(bn, bn // 8) bf16 with entry [j, j // 8] = 2 ** (j % 8): a 0/1 row
    times this matrix is its packed bytes.  Every product and partial sum is
    an integer below 256, exact from bf16 inputs with f32 accumulation."""
    j = jnp.arange(bn)
    own_byte = (j // 8)[:, None] == jnp.arange(bn // 8)[None, :]
    return jnp.where(own_byte, (1 << (j % 8))[:, None],
                     0).astype(jnp.bfloat16)


def _tiling(n: int, block: int) -> tuple[int, int, int]:
    """(bm, bn, npad): row tile, column tile and padded size, shared by the
    kernel and its XLA twin so both emit the same (npad, npad // 8) array."""
    bm = min(block, max(8, -(-n // 8) * 8))
    bm = max(8, bm - bm % 8)
    npad = -(-n // bm) * bm
    if npad <= _TILE_COLS:
        return bm, npad, npad
    step = math.lcm(bm, _TILE_COLS)
    return bm, _TILE_COLS, -(-n // step) * step


def _nbr_kernel(x_ref, y_ref, pack_ref, cnt_ref, adj_ref, *, eps_sq, n, bn):
    """One (bm, bn) tile: threshold at ε² in registers, emit the packed
    adjacency block and accumulate per-row counts over the sequential j
    axis.  The (bm, bn) float32 tile never leaves VMEM."""
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)          # (bm, F)
    y = y_ref[...].astype(jnp.float32)          # (bn, F)
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    yy = jnp.sum(y * y, axis=1, keepdims=True)
    xy = jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    d2 = jnp.maximum(xx + yy.T - 2.0 * xy, 0.0)
    # mask padding columns so zero-padded rows never count as neighbours
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    adj = (d2 <= eps_sq) & (col < n)

    @pl.when(j == 0)
    def _():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    cnt_ref[...] += jnp.sum(adj.astype(jnp.int32), axis=1, keepdims=True)
    bits = jnp.where(adj, 1.0, 0.0).astype(jnp.bfloat16)
    packed = jax.lax.dot(bits, pack_ref[...],
                         preferred_element_type=jnp.float32)
    adj_ref[...] = packed.astype(jnp.int32).astype(jnp.uint8)


@functools.partial(jax.jit,
                   static_argnames=("eps_sq", "block", "interpret"))
def _neighbor_adjacency_pallas(x, *, eps_sq: float, block: int,
                               interpret: bool):
    n, f = x.shape
    bm, bn, np_ = _tiling(n, block)
    x = jnp.pad(x, ((0, np_ - n), (0, 0)))
    kern = functools.partial(_nbr_kernel, eps_sq=eps_sq, n=n, bn=bn)
    counts, packed = pl.pallas_call(
        kern,
        grid=(np_ // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, f), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, f), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, bn // 8), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, bn // 8), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, np_ // 8), jnp.uint8),
        ],
        interpret=interpret,
    )(x, x, _pack_matrix(bn))
    return counts.reshape(np_), packed


@functools.partial(jax.jit, static_argnames=("eps_sq", "block"))
def _neighbor_adjacency_xla(x, *, eps_sq: float, block: int):
    """Tiled pure-jnp twin of the Pallas kernel: identical blocking,
    thresholding and bit packing, compiled by XLA.  Peak memory is one
    (bm, Npad) strip, never the full (N, N) matrix."""
    n, f = x.shape
    bm, _, np_ = _tiling(n, block)
    x = jnp.pad(x.astype(jnp.float32), ((0, np_ - n), (0, 0)))
    yy = jnp.sum(x * x, axis=1)
    col_ok = jnp.arange(np_) < n

    def one_strip(xb):                           # (bm, F)
        xx = jnp.sum(xb * xb, axis=1, keepdims=True)
        xy = jax.lax.dot_general(xb, x, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        d2 = jnp.maximum(xx + yy[None, :] - 2.0 * xy, 0.0)
        adj = (d2 <= eps_sq) & col_ok[None, :]
        return jnp.sum(adj, axis=1).astype(jnp.int32), _pack_bits(adj)

    counts, packed = jax.lax.map(one_strip, x.reshape(np_ // bm, bm, f))
    return counts.reshape(np_), packed.reshape(np_, np_ // 8)


def neighbor_adjacency(x, eps, *, block: int = 128, impl: str = "auto"):
    """(N, F), ε -> (counts (Npad,) int32, packed (Npad, Npad/8) uint8).

    The streaming DBSCAN front-end: per-row ε-neighbour counts (self
    included) and the bit-packed adjacency matrix, produced without ever
    materializing (N, N) float32 in HBM.  Rows ≥ N are zero padding with
    zero counts and empty adjacency; callers slice ``[:N]`` as needed.
    """
    resolved = dispatch.resolve(impl)
    eps_sq = float(eps) * float(eps)
    if resolved in ("xla", "ref"):
        return _neighbor_adjacency_xla(x, eps_sq=eps_sq, block=block)
    return _neighbor_adjacency_pallas(
        x, eps_sq=eps_sq, block=block,
        interpret=(resolved == "pallas_interpret"))


def neighbor_count(x, eps, *, block: int = 128, impl: str = "auto",
                   interpret: bool | None = None):
    """(N, F), ε -> (N,) int32 neighbour counts (self included)."""
    if interpret is not None:                    # legacy kwarg compatibility
        impl = "pallas_interpret" if interpret else "pallas"
    counts, _ = neighbor_adjacency(x, eps, block=block, impl=impl)
    return counts[:x.shape[0]]
