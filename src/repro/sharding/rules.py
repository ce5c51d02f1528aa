"""Sharding rules: logical axes -> PartitionSpec/NamedSharding trees.

Logical axes:
  'batch' — data-parallel dim of activations/inputs; maps to ('pod','data') on
            the multi-pod mesh and 'data' on the single-pod mesh.
  'data'  — FSDP/ZeRO param+optimizer shard axis (within-pod only: params are
            replicated across pods, gradients all-reduce over 'pod').
  'model' — tensor/expert/sequence-parallel axis.

Param specs are derived from leaf names (see models/*), with any extra leading
stacking axes (scan-over-layers, zamba2 groups, LoRA invocations) replicated.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]):
    global _MESH
    _MESH = mesh


def current_mesh() -> Optional[Mesh]:
    return _MESH


def _resolve(axes, mesh) -> P:
    """Map logical axis tuple -> PartitionSpec valid on ``mesh``."""
    names = set(mesh.axis_names)
    out = []
    for a in axes:
        if a == "batch":
            out.append(("pod", "data") if "pod" in names else
                       ("data" if "data" in names else None))
        elif isinstance(a, tuple):
            sub = tuple(x for x in a if x in names)
            out.append(sub if sub else None)
        elif a is None or a in names:
            out.append(a)
        else:
            out.append(None)
    return P(*out)


def named(axes) -> Optional[NamedSharding]:
    if _MESH is None:
        return None
    return NamedSharding(_MESH, _resolve(axes, _MESH))


def maybe_constrain(x, axes):
    """with_sharding_constraint if a mesh is active; no-op otherwise."""
    s = named(axes)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


def act_spec(tun):
    return ("batch", "model" if tun.seq_parallel else None, None)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

_IN_MATS = {"wq", "wk", "wv", "wi", "wg", "in_proj", "router", "patch_proj",
            "frame_proj", "head", "lora_a"}
_OUT_MATS = {"wo", "out_proj"}


def _param_axes(path_names, shape):
    name = path_names[-1]
    in_moe = "moe" in path_names and "shared" not in path_names \
        and "dense" not in path_names
    if name == "embed":
        base = ("model", "data")
    elif name == "conv_w":
        base = (None, None, "model")
    elif name == "lora_b":
        base = (None, "model")
    elif in_moe and name in ("wi", "wg"):
        base = ("model", "data", None)        # (E, D, Fe): EP over model
    elif in_moe and name == "wo":
        base = ("model", None, "data")        # (E, Fe, D)
    elif name in _IN_MATS:
        base = ("data", "model")
    elif name in _OUT_MATS:
        base = ("model", "data")
    else:
        base = (None,) * min(len(shape), 1)   # norms/biases/scalars: replicate
        return (None,) * (len(shape) - len(base)) + base
    lead = len(shape) - len(base)
    assert lead >= 0, (path_names, shape)
    return (None,) * lead + base


def _path_names(path):
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:
            out.append(str(k))
    return tuple(out)


def param_axes_tree(params, zero3: bool = True):
    """Tree of logical-axis tuples parallel to ``params`` (works on
    ShapeDtypeStructs too)."""
    def rule(path, leaf):
        axes = _param_axes(_path_names(path), leaf.shape)
        if not zero3:
            axes = tuple(None if a == "data" else a for a in axes)
        return axes
    return jax.tree_util.tree_map_with_path(rule, params)


def param_shardings(params, zero3: bool = True):
    return tree_shardings(param_axes_tree(params, zero3))


_NON_PARAM_TOP = {"count", "step", "rng"}


def state_axes_tree(state, zero3: bool = True):
    """Axes for a full train state {"params", "opt": {"m","v","count"}, "ef"}.

    Optimizer moments mirror the parameter sharding (ZeRO-3 via GSPMD); int8
    moment scales (trailing tuple index "1") drop the last axis.
    """
    def rule(path, leaf):
        names = _path_names(path)
        if names[0] in _NON_PARAM_TOP or names[-1] in _NON_PARAM_TOP:
            return ()
        # strip trailing tuple indices (int8 moment (q, scale) pairs)
        core = list(names)
        tup = []
        while core and core[-1].isdigit():
            tup.append(core.pop())
        if not core:
            return (None,) * len(leaf.shape)
        if tup and tup[-1] == "1":  # scale leaf: param axes minus last dim
            # reconstruct the quantized leaf's axes from the scale's shape
            axes = _param_axes(tuple(core), leaf.shape)
            axes = axes[:-1] + (None,)
        else:
            axes = _param_axes(tuple(core), leaf.shape)
        if not zero3:
            axes = tuple(None if a == "data" else a for a in axes)
        return axes
    return jax.tree_util.tree_map_with_path(rule, state)


# ---------------------------------------------------------------------------
# input / cache specs
# ---------------------------------------------------------------------------


def _tp_size() -> int:
    return int(_MESH.shape.get("model", 1)) if _MESH is not None else 1


def _cache_axes(name: str, shape, seq_axis: int = -3):
    r = len(shape)
    if name in ("k", "v", "k0", "v0", "xk", "xv"):
        # (B, S, K, hd). When kv-heads divide tp, shard heads over 'model'
        # (zero-collective attention). Otherwise shard the SEQUENCE
        # (context-parallel serving): head-dim sharding forces XLA into
        # involuntary full rematerialization (whole cache resharded per
        # decoded token), while sequence sharding always divides, keeps the
        # per-step append local, and reduces attention with one tiny psum of
        # (B,H,hd) partials + softmax stats. §Perf iterations 0a/0b.
        # A sequence-major self-attention cache (seq_axis -4) is
        # (S, K, B, hd) and takes the same axes, permuted.
        seq_major = seq_axis == -4 and name in ("k", "v", "k0", "v0")
        b_dim, k_dim = (r - 2, r - 3) if seq_major else (r - 4, r - 2)
        tp = _tp_size()
        heads_ok = shape[k_dim] % tp == 0
        if shape[b_dim] == 1:
            base = ((None, "data", "model", None) if heads_ok else
                    (None, ("data", "model"), None, None))
        else:
            base = (("batch", None, "model", None) if heads_ok else
                    ("batch", "model", None, None))
        if seq_major:
            base = (base[1], base[2], base[0], base[3])
    elif name == "ssm":
        b = "batch" if shape[r - 4] > 1 else None
        base = (b, "model", None, None)           # (B, H, N, P)
    elif name == "conv":
        b = "batch" if shape[r - 3] > 1 else None
        base = (b, None, "model")                 # (B, k-1, Cd)
    elif name == "pos":
        return ()
    else:
        base = ("batch",) + (None,) * max(r - 1, 0)
        base = base[:r]
    return (None,) * (r - len(base)) + base


def cache_axes_tree(cache, seq_axis: int = -3):
    """Axes of every cache leaf; ``seq_axis`` is the model's
    ``cache_seq_axis``."""
    def rule(path, leaf):
        return _cache_axes(_path_names(path)[-1], leaf.shape, seq_axis)
    return jax.tree_util.tree_map_with_path(rule, cache)


def batch_axes_tree(batch):
    def rule(path, leaf):
        name = _path_names(path)[-1]
        if name == "pos" or len(leaf.shape) == 0:
            return ()
        if leaf.shape[0] == 1:  # unshardable unit batch (long-context decode)
            return (None,) * len(leaf.shape)
        return ("batch",) + (None,) * (len(leaf.shape) - 1)
    return jax.tree_util.tree_map_with_path(rule, batch)


def _is_axes(x) -> bool:
    """An axes tuple holds str/None entries (or tuples of ONLY str, e.g.
    ('data','model') joint sharding). This distinguishes axes from pytree
    tuples like int8-moment (q, scale) pairs, whose elements are themselves
    axes tuples containing None."""
    if not isinstance(x, tuple):
        return False
    return all(e is None or isinstance(e, str) or
               (isinstance(e, tuple) and e and
                all(isinstance(s, str) for s in e)) for e in x)


def tree_shardings(axes_tree):
    return jax.tree_util.tree_map(lambda a: named(a), axes_tree,
                                  is_leaf=_is_axes)
