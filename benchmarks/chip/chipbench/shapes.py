"""Operations and bytes that the served requests need, from shapes alone.

``p`` is a configuration's ``program`` block (``configs/<name>.json``).
Counts are of the work the requests need, not of what the program happens
to compute: a prompt counts at its own length, not at the batch's padded
one, finished and padding rows count for nothing, and attention counts the
positions a request can see, not the cache's capacity.  A matmul of
``m x k`` by ``k x n`` is ``2 m k n`` operations.

For the state-space family the count is that of the recurrence (state
update and readout per token); the chunked prefill does more, and that
extra is the program's cost, not the request's.
"""
from __future__ import annotations

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def vocab_padded(p: dict) -> int:
    return -(-int(p["vocab"]) // 256) * 256


def head_dim(p: dict) -> int:
    return int(p.get("head_dim") or p["d_model"] // p["n_heads"])


def _ssm_dims(p: dict):
    s = p["ssm"]
    d_inner = s["expand"] * p["d_model"]
    H = d_inner // s["head_dim"]
    conv_dim = d_inner + 2 * s["n_groups"] * s["d_state"]
    return s, d_inner, H, conv_dim


def layer_matmul_params(p: dict) -> int:
    """Weights of one layer that a token multiplies through."""
    D = p["d_model"]
    if p["family"] == "ssm":
        s, d_inner, H, conv_dim = _ssm_dims(p)
        return D * (2 * d_inner + 2 * s["n_groups"] * s["d_state"] + H) \
            + d_inner * D
    hd = head_dim(p)
    q, kv = p["n_heads"] * hd, p["n_kv_heads"] * hd
    return D * q + 2 * D * kv + q * D + 3 * D * p["d_ff"]


def weight_bytes(p: dict) -> int:
    """Every parameter once, in the type it is served in."""
    b = DTYPE_BYTES[p["dtype"]]
    D, L = p["d_model"], p["n_layers"]
    n = vocab_padded(p) * D + D                       # embedding, final norm
    if p["family"] == "ssm":
        s, d_inner, H, conv_dim = _ssm_dims(p)
        per = layer_matmul_params(p) + D + s["d_conv"] * conv_dim \
            + conv_dim + d_inner
        return b * (n + L * per) + 4 * L * 3 * H      # A_log, D, dt_bias f32
    hd = head_dim(p)
    per = layer_matmul_params(p) + 2 * D
    if p.get("qkv_bias"):
        per += (p["n_heads"] + 2 * p["n_kv_heads"]) * hd
    if not p.get("tie_embeddings", True):
        n += vocab_padded(p) * D
    return b * (n + L * per)


def _token_layer_flops(p: dict) -> int:
    """Per token and layer, beyond matmuls and attention: the recurrence."""
    if p["family"] != "ssm":
        return 0
    s, d_inner, H, conv_dim = _ssm_dims(p)
    return 5 * H * s["d_state"] * s["head_dim"] + 2 * s["d_conv"] * conv_dim


def _attn_flops(p: dict, ctx) -> np.ndarray:
    """QK^T and PV for one query over ``ctx`` visible positions, all layers."""
    if p["family"] == "ssm":
        return np.zeros_like(np.asarray(ctx, np.float64))
    return 4.0 * p["n_layers"] * p["n_heads"] * head_dim(p) * np.asarray(
        ctx, np.float64)


def prefill_flops(p: dict, prompt_len) -> np.ndarray:
    """A prompt of ``prompt_len`` tokens, then logits at its last position."""
    s = np.asarray(prompt_len, np.float64)
    L, D = p["n_layers"], p["d_model"]
    dense = s * (2.0 * L * layer_matmul_params(p) + L * _token_layer_flops(p))
    # causal: query i sees i + 1 positions; sum over i of (i + 1)
    attn = 0.0 if p["family"] == "ssm" else \
        4.0 * L * p["n_heads"] * head_dim(p) * s * (s + 1) / 2
    return dense + attn + 2.0 * D * p["vocab"]


def decode_flops(p: dict, ctx) -> np.ndarray:
    """One decoded token that sees ``ctx`` positions (itself included)."""
    L, D = p["n_layers"], p["d_model"]
    return (2.0 * L * layer_matmul_params(p) + L * _token_layer_flops(p)
            + 2.0 * D * p["vocab"]) + _attn_flops(p, ctx)


def decode_row_bytes(p: dict, ctx) -> np.ndarray:
    """Per active row and step: cache or state that the step must touch.

    Attention reads the keys and values of the visible positions and writes
    one new pair; the state-space family reads and writes its whole state
    (the f32 SSD state and the convolution window)."""
    L = p["n_layers"]
    b = DTYPE_BYTES[p["dtype"]]
    ctx = np.asarray(ctx, np.float64)
    if p["family"] == "ssm":
        s, d_inner, H, conv_dim = _ssm_dims(p)
        state = 4 * H * s["d_state"] * s["head_dim"] \
            + b * (s["d_conv"] - 1) * conv_dim
        return np.full_like(ctx, 2.0 * L * state)
    kv = 2 * p["n_kv_heads"] * head_dim(p) * b * L
    return kv * ctx + kv


def decode_step_bytes(p: dict, row_ctx) -> float:
    """One decode step over the active rows with contexts ``row_ctx``."""
    return float(weight_bytes(p) + np.sum(decode_row_bytes(p, row_ctx)))


def decode_steps(p: dict, calls, prompt_len, gen) -> np.ndarray:
    """(steps, 2): operations and bytes of every decode step that the
    calls' real rows needed.  Row ``r`` of a call lives for its first
    ``gen[r]`` steps, and at step ``i`` it sees ``prompt_len[r] + i + 1``
    positions."""
    out = []
    for c in calls:
        p_r = np.asarray(prompt_len)[c.requests]
        g_r = np.asarray(gen)[c.requests]
        for i in range(c.steps):
            ctx = p_r[g_r > i] + i + 1
            out.append((float(decode_flops(p, ctx).sum()),
                        decode_step_bytes(p, ctx)))
    return np.array(out, np.float64).reshape(-1, 2)
