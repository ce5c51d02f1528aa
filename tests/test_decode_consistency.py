"""Prefill+decode must reproduce the full-forward logits: the strongest
correctness check on KV/SSM cache handling across all families."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DEFAULT_TUNABLES, ShapeSpec
from repro.models import model as M
from repro.train.step import make_serve_step
from tests.conftest import tiny

TUN = DEFAULT_TUNABLES


def _grow_kv(cfg, cache, extra):
    axis = M.cache_seq_axis(cfg)

    def grow(path, a):
        name = str(getattr(path[-1], "key", ""))
        if name in ("k", "v", "k0", "v0") and a.ndim >= 4:
            pad = [(0, 0)] * a.ndim
            pad[axis] = (0, extra)
            return jnp.pad(a, pad)
        return a
    return jax.tree_util.tree_map_with_path(grow, cache)


@pytest.mark.parametrize("arch", [
    "qwen2-1.5b", "gemma2-9b", "qwen3-14b", "deepseek-moe-16b",
    "mamba2-1.3b", "zamba2-7b", "paligemma-3b",
])
def test_decode_matches_forward(arch, rng_key):
    cfg = tiny(arch, dtype="float32")
    # capacity dropping is (by design) batch-dependent; disable it so the
    # equality check isolates cache handling
    tun = TUN.replace(capacity_factor=64.0) if cfg.moe else TUN
    P, G = 32, 4
    params = M.init(rng_key, cfg)

    # for VLM, seq = patches + text: pad the shape so the TEXT is P+G long
    seq = P + G + (cfg.num_patches if cfg.family == "vlm" else 0)
    full = M.make_batch(rng_key, cfg, ShapeSpec("f", seq, 2, "prefill"))
    tokens = full["tokens"]

    def fwd(upto):
        b = dict(full)
        b["tokens"] = tokens[:, :upto]
        logits, _, _ = M.forward(params, cfg, b, tun)
        return logits[:, -1]

    pf = dict(full)
    pf["tokens"] = tokens[:, :P]
    logits_pf, cache = M.prefill(params, cfg, pf, tun)
    cache = _grow_kv(cfg, cache, G)

    np.testing.assert_allclose(np.asarray(logits_pf[:, 0]),
                               np.asarray(fwd(P)), rtol=2e-4, atol=2e-4)

    offset = cfg.num_patches if cfg.family == "vlm" else 0
    for i in range(G):
        step = {"tokens": tokens[:, P + i:P + i + 1],
                "pos": jnp.asarray(P + i + offset, jnp.int32)}
        logits, cache = M.decode(params, cfg, step, cache, tun)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(fwd(P + i + 1)),
            rtol=2e-4, atol=2e-4,
            err_msg=f"{arch} decode step {i}")


def test_encdec_decode_matches_forward(rng_key):
    cfg = tiny("seamless-m4t-large-v2", dtype="float32")
    P, G = 16, 3
    params = M.init(rng_key, cfg)
    full = M.make_batch(rng_key, cfg, ShapeSpec("f", 2 * (P + G), 2, "prefill"))
    tokens = full["tokens"]

    def fwd(upto):
        b = {"frames": full["frames"], "tokens": tokens[:, :upto]}
        logits, _, _ = M.forward(params, cfg, b, TUN)
        return logits[:, -1]

    pf = {"frames": full["frames"], "tokens": tokens[:, :P]}
    _, cache = M.prefill(params, cfg, pf, TUN)
    cache = _grow_kv(cfg, cache, G)
    # xk/xv must NOT grow (encoder memory fixed) — undo for cross keys
    for i in range(G):
        step = {"tokens": tokens[:, P + i:P + i + 1],
                "pos": jnp.asarray(P + i, jnp.int32)}
        logits, cache = M.decode(params, cfg, step, cache, TUN)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(fwd(P + i + 1)),
            rtol=2e-4, atol=2e-4, err_msg=f"encdec step {i}")


# The decode step writes each token into the donated cache in place.  Cases:
# a capacity padded far past the tokens (the engine's cache_len 512), the
# MoE dense first layer with its own unstacked cache, and alternating
# sliding windows narrow enough to mask inside the decoded span.
INPLACE_CASES = {
    "padded_capacity": ("qwen2-1.5b", {}, 512),
    "layer0": ("deepseek-moe-16b", {}, 64),
    "alternating_window": ("gemma2-9b", {"window": 8}, 64),
}


def _inplace_case(name):
    arch, kw, capacity = INPLACE_CASES[name]
    cfg = tiny(arch, dtype="float32", **kw)
    tun = TUN.replace(capacity_factor=64.0) if cfg.moe else TUN
    return cfg, tun, capacity


@pytest.mark.parametrize("case", list(INPLACE_CASES))
def test_inplace_decode_matches_forward_token_for_token(case, rng_key):
    cfg, tun, capacity = _inplace_case(case)
    if case == "layer0":
        assert cfg.moe.first_layer_dense
    P, G = 24, 6
    params = M.init(rng_key, cfg)
    prompt = M.make_batch(rng_key, cfg, ShapeSpec("p", P, 2, "prefill"))
    logits, cache = M.prefill(params, cfg, prompt, tun)
    cache = _grow_kv(cfg, cache, capacity - P)
    step = jax.jit(make_serve_step(cfg, tun), donate_argnums=(1,))

    # greedy decode through the donated step the engine runs
    tokens, served = [prompt["tokens"]], [logits[:, -1]]
    for i in range(G):
        tok = jnp.argmax(served[-1][:, :cfg.vocab], -1)[:, None].astype(
            jnp.int32)
        tokens.append(tok)
        logits, cache = step(params, cache,
                             {"tokens": tok,
                              "pos": jnp.asarray(P + i, jnp.int32)})
        served.append(logits[:, -1])
    seq = jnp.concatenate(tokens, 1)
    full, _, _ = M.forward(params, cfg, {"tokens": seq}, tun)
    want = np.asarray(full[:, P - 1:])
    got = np.stack([np.asarray(x) for x in served], 1)
    np.testing.assert_array_equal(got[..., :cfg.vocab].argmax(-1),
                                  want[..., :cfg.vocab].argmax(-1))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4,
                               err_msg=case)


@pytest.mark.parametrize("case", list(INPLACE_CASES))
def test_compiled_serve_step_aliases_the_donated_cache(case):
    """Every cache output reuses its donated input buffer, and nothing in
    the program copies a whole cache leaf: the regression this guards is
    a step that stacks each layer's updated cache into a fresh buffer and
    copies it back into the donated one."""
    cfg, tun, capacity = _inplace_case(case)
    params = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 2, capacity))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    text = jax.jit(make_serve_step(cfg, tun), donate_argnums=(1,)).lower(
        params, cache, batch).compile().as_text()
    alias = re.search(r"input_output_alias=\{((?:\s*\{\d+\}: \([^)]*\),?)*)",
                      text)
    assert alias is not None
    outputs = {int(o) for o in re.findall(r"\{(\d+)\}: \(", alias.group(1))}
    leaves = jax.tree_util.tree_leaves(cache)
    assert outputs == set(range(1, 1 + len(leaves)))
    for leaf in leaves:
        shape = "f32[%s]" % ",".join(map(str, leaf.shape))
        copies = [ln for ln in text.splitlines()
                  if re.search(r"= %s\{[^}]*\} copy\(" % re.escape(shape),
                               ln)]
        assert not copies, copies
