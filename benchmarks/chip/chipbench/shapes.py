"""Operations and bytes that the served requests need, from shapes alone.

``p`` is a configuration's ``program`` block (``configs/<name>.json``).
Counts are of the work the requests need, not of what the program happens
to compute: a prompt counts at its own length, not at the batch's padded
one, finished and padding rows count for nothing, and attention counts the
positions a request can see, not the cache's capacity.  A matmul of
``m x k`` by ``k x n`` is ``2 m k n`` operations.

Each model family keeps its counts in a file of its own,
``counts/<family>.py``, found by the block's ``family`` (``registry``):
``weight_bytes(p)``, ``prefill_flops(p, prompt_len)``,
``decode_flops(p, ctx)`` and ``decode_row_bytes(p, ctx)``.  A family with
no such file is an error, never counted as another.  The functions here
dispatch to it, and add up whole decode steps; ``at(root)`` binds them to
the counts files of one folder, as a per-layer reader gets them
(``run.shapes``).
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import numpy as np

from chipbench import registry

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def vocab_padded(p: dict) -> int:
    return -(-int(p["vocab"]) // 256) * 256


@functools.lru_cache(maxsize=None)
def family(name: str, root: str = registry.ROOT):
    """The counts module of the family ``name``, ``counts/<name>.py``."""
    return registry.counts(name, root)


def weight_bytes(p: dict, root: str = registry.ROOT) -> int:
    """Every parameter once, in the type it is served in."""
    return family(p["family"], root).weight_bytes(p)


def prefill_flops(p: dict, prompt_len, root: str = registry.ROOT):
    """A prompt of ``prompt_len`` tokens, then logits at its last position."""
    return family(p["family"], root).prefill_flops(p, prompt_len)


def decode_flops(p: dict, ctx, root: str = registry.ROOT):
    """One decoded token that sees ``ctx`` positions (itself included)."""
    return family(p["family"], root).decode_flops(p, ctx)


def decode_row_bytes(p: dict, ctx, root: str = registry.ROOT):
    """Per active row and step: cache or state that the step must touch."""
    return family(p["family"], root).decode_row_bytes(p, ctx)


def decode_step_bytes(p: dict, row_ctx, root: str = registry.ROOT) -> float:
    """One decode step over the active rows with contexts ``row_ctx``."""
    return float(weight_bytes(p, root)
                 + np.sum(decode_row_bytes(p, row_ctx, root)))


def decode_steps(p: dict, calls, prompt_len, gen,
                 root: str = registry.ROOT) -> np.ndarray:
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
            out.append((float(decode_flops(p, ctx, root).sum()),
                        decode_step_bytes(p, ctx, root)))
    return np.array(out, np.float64).reshape(-1, 2)


def at(root: str) -> SimpleNamespace:
    """The functions above, bound to the counts files under ``root``."""
    return SimpleNamespace(**{f.__name__: functools.partial(f, root=root)
                              for f in (weight_bytes, prefill_flops,
                                        decode_flops, decode_row_bytes,
                                        decode_step_bytes, decode_steps)})
