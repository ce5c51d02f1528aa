"""Operations and bytes of the dense decoder family (``"dense"``): every
layer holds grouped-query attention, with an optional bias on its query,
key and value projections, and a gated MLP.  ``chipbench/shapes.py`` says
what is counted and dispatches here by the program block's ``family``."""
from __future__ import annotations

import numpy as np

from chipbench.shapes import DTYPE_BYTES, vocab_padded


def head_dim(p: dict) -> int:
    return int(p.get("head_dim") or p["d_model"] // p["n_heads"])


def layer_matmul_params(p: dict) -> int:
    """Weights of one layer that a token multiplies through."""
    D = p["d_model"]
    hd = head_dim(p)
    q, kv = p["n_heads"] * hd, p["n_kv_heads"] * hd
    return D * q + 2 * D * kv + q * D + 3 * D * p["d_ff"]


def weight_bytes(p: dict) -> int:
    """Every parameter once, in the type it is served in."""
    b = DTYPE_BYTES[p["dtype"]]
    D, L = p["d_model"], p["n_layers"]
    n = vocab_padded(p) * D + D                       # embedding, final norm
    hd = head_dim(p)
    per = layer_matmul_params(p) + 2 * D
    if p.get("qkv_bias"):
        per += (p["n_heads"] + 2 * p["n_kv_heads"]) * hd
    if not p.get("tie_embeddings", True):
        n += vocab_padded(p) * D
    return b * (n + L * per)


def prefill_flops(p: dict, prompt_len) -> np.ndarray:
    """A prompt of ``prompt_len`` tokens, then logits at its last position."""
    s = np.asarray(prompt_len, np.float64)
    L, D = p["n_layers"], p["d_model"]
    dense = s * (2.0 * L * layer_matmul_params(p))
    # causal: query i sees i + 1 positions; sum over i of (i + 1)
    attn = 4.0 * L * p["n_heads"] * head_dim(p) * s * (s + 1) / 2
    return dense + attn + 2.0 * D * p["vocab"]


def decode_flops(p: dict, ctx) -> np.ndarray:
    """One decoded token that sees ``ctx`` positions (itself included):
    the matmuls, QK^T and PV over the visible positions, the logits."""
    L, D = p["n_layers"], p["d_model"]
    return (2.0 * L * layer_matmul_params(p) + 2.0 * D * p["vocab"]) \
        + 4.0 * L * p["n_heads"] * head_dim(p) * np.asarray(ctx, np.float64)


def decode_row_bytes(p: dict, ctx) -> np.ndarray:
    """Per active row and step: the keys and values of the visible
    positions read, and one new pair written."""
    L = p["n_layers"]
    b = DTYPE_BYTES[p["dtype"]]
    ctx = np.asarray(ctx, np.float64)
    kv = 2 * p["n_kv_heads"] * head_dim(p) * b * L
    return kv * ctx + kv
