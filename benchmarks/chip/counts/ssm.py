"""Operations and bytes of the state-space family (``"ssm"``): every layer
is a Mamba-2 mixer, an SSD state per head and a short convolution window.
``chipbench/shapes.py`` says what is counted and dispatches here by the
program block's ``family``.

The count is that of the recurrence (state update and readout per token);
the chunked prefill does more, and that extra is the program's cost, not
the request's.
"""
from __future__ import annotations

import numpy as np

from chipbench.shapes import DTYPE_BYTES, vocab_padded


def ssm_dims(p: dict):
    s = p["ssm"]
    d_inner = s["expand"] * p["d_model"]
    H = d_inner // s["head_dim"]
    conv_dim = d_inner + 2 * s["n_groups"] * s["d_state"]
    return s, d_inner, H, conv_dim


def layer_matmul_params(p: dict) -> int:
    """Weights of one layer that a token multiplies through."""
    D = p["d_model"]
    s, d_inner, H, conv_dim = ssm_dims(p)
    return D * (2 * d_inner + 2 * s["n_groups"] * s["d_state"] + H) \
        + d_inner * D


def weight_bytes(p: dict) -> int:
    """Every parameter once, in the type it is served in."""
    b = DTYPE_BYTES[p["dtype"]]
    D, L = p["d_model"], p["n_layers"]
    n = vocab_padded(p) * D + D                       # embedding, final norm
    s, d_inner, H, conv_dim = ssm_dims(p)
    per = layer_matmul_params(p) + D + s["d_conv"] * conv_dim \
        + conv_dim + d_inner
    return b * (n + L * per) + 4 * L * 3 * H          # A_log, D, dt_bias f32


def token_layer_flops(p: dict) -> int:
    """Per token and layer, beyond the matmuls: the recurrence and the
    convolution."""
    s, d_inner, H, conv_dim = ssm_dims(p)
    return 5 * H * s["d_state"] * s["head_dim"] + 2 * s["d_conv"] * conv_dim


def prefill_flops(p: dict, prompt_len) -> np.ndarray:
    """A prompt of ``prompt_len`` tokens, then logits at its last position."""
    s = np.asarray(prompt_len, np.float64)
    L, D = p["n_layers"], p["d_model"]
    dense = s * (2.0 * L * layer_matmul_params(p) + L * token_layer_flops(p))
    return dense + 2.0 * D * p["vocab"]


def decode_flops(p: dict, ctx) -> np.ndarray:
    """One decoded token, whatever the ``ctx`` positions before it."""
    L, D = p["n_layers"], p["d_model"]
    return (2.0 * L * layer_matmul_params(p) + L * token_layer_flops(p)
            + 2.0 * D * p["vocab"]) + np.zeros_like(
                np.asarray(ctx, np.float64))


def decode_row_bytes(p: dict, ctx) -> np.ndarray:
    """Per active row and step: the whole state read and written, the f32
    SSD state and the convolution window."""
    L = p["n_layers"]
    b = DTYPE_BYTES[p["dtype"]]
    ctx = np.asarray(ctx, np.float64)
    s, d_inner, H, conv_dim = ssm_dims(p)
    state = 4 * H * s["d_state"] * s["head_dim"] \
        + b * (s["d_conv"] - 1) * conv_dim
    return np.full_like(ctx, 2.0 * L * state)
