"""Sharding-rule derivation on full-config abstract trees, and MoE dispatch
exactness against a naive per-token reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DEFAULT_TUNABLES, SHAPES
from repro.configs.registry import ARCHS, get_config
from repro.models import model as M
from repro.models import moe as MOE
from repro.optim.adamw import OptConfig
from repro.sharding import rules
from repro.train.step import init_train_state
from tests.conftest import tiny


@pytest.mark.parametrize("arch", ARCHS)
def test_param_axes_match_ranks(arch):
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
    axes = rules.param_axes_tree(shapes)
    flat_s = jax.tree_util.tree_leaves(shapes)
    flat_a = jax.tree_util.tree_leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_s) == len(flat_a)
    for s, a in zip(flat_s, flat_a):
        assert len(a) == len(s.shape), (s.shape, a)


def test_embed_and_expert_specs():
    cfg = get_config("deepseek-moe-16b")
    shapes = jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0), cfg))
    axes = rules.param_axes_tree(shapes)
    assert axes["embed"] == ("model", "data")
    assert axes["layers"]["moe"]["wi"] == (None, "model", "data", None)
    assert axes["layers"]["moe"]["wo"] == (None, "model", None, "data")
    # shared experts are plain mlps: FSDP x TP
    assert axes["layers"]["moe"]["shared"]["wi"] == (None, "data", "model")
    assert axes["layers"]["attn"]["wo"] == (None, "model", "data")
    # zero3 off removes the data axis from params
    axes2 = rules.param_axes_tree(shapes, zero3=False)
    assert axes2["embed"] == ("model", None)


def test_state_axes_int8_moments(rng_key):
    cfg = tiny("qwen2-1.5b")
    oc = OptConfig(moments_dtype="int8")
    state = jax.eval_shape(
        lambda: init_train_state(rng_key, cfg, oc, DEFAULT_TUNABLES))
    axes = rules.state_axes_tree(state)
    # moment q mirrors the param; scale drops the last axis
    assert axes["opt"]["m"]["embed"][0] == ("model", "data")
    assert axes["opt"]["m"]["embed"][1] == ("model", None)
    assert axes["opt"]["count"] == ()


def test_batch_and_cache_axes():
    cfg = get_config("qwen3-14b")
    specs = M.input_specs(cfg, SHAPES["train_4k"])
    axes = rules.batch_axes_tree(specs)
    assert axes["tokens"] == ("batch", None)
    cache = M.cache_specs(cfg, SHAPES["decode_32k"])
    seq_axis = M.cache_seq_axis(cfg)
    caxes = rules.cache_axes_tree(cache, seq_axis)
    # the stacked cache is sequence-major, (L, S, K, B, hd); without a live
    # mesh tp=1 -> kv-heads divide -> head sharding
    assert caxes["k"][3] == "batch" and caxes["k"][2] == "model"
    # with a 16-way 'model' axis, qwen3 kv=8 doesn't divide -> seq sharding
    from repro.launch.mesh import make_host_mesh
    import jax
    from jax.sharding import Mesh
    import numpy as np
    fake = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    rules.set_mesh(fake)
    try:
        caxes1 = rules.cache_axes_tree(cache, seq_axis)
        assert caxes1["k"][2] == "model"   # tp=1 divides
    finally:
        rules.set_mesh(None)
    # unit batch (long_500k-style): no batch sharding, seq over both axes
    c1 = M.cache_specs(get_config("mamba2-1.3b"), SHAPES["long_500k"])
    a1 = rules.cache_axes_tree(c1)
    assert a1["ssm"][1] is None      # B==1 -> unsharded batch


def test_moe_dispatch_matches_naive_reference(rng_key):
    """With ample capacity the dispatch/compute/combine path must equal the
    naive per-token top-k expert sum exactly."""
    cfg = tiny("deepseek-moe-16b")
    m = cfg.moe
    p = MOE.moe_init(rng_key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y, aux = MOE.moe_apply(p, x, cfg, capacity_factor=float(m.num_experts))

    # naive reference
    xt = x.reshape(-1, cfg.d_model)
    logits = xt @ p["router"]
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    gate, idx = jax.lax.top_k(probs, m.top_k)
    gate = gate / gate.sum(-1, keepdims=True)

    def expert(e, t):
        h = jax.nn.silu(t @ p["wg"][e]) * (t @ p["wi"][e])
        return h @ p["wo"][e]

    ref = jnp.zeros_like(xt)
    for t in range(xt.shape[0]):
        acc = jnp.zeros((cfg.d_model,))
        for k in range(m.top_k):
            acc += gate[t, k] * expert(idx[t, k], xt[t])
        ref = ref.at[t].set(acc)
    from repro.models.layers import mlp_apply
    if m.num_shared:
        ref = ref + mlp_apply(p["shared"], xt[None])[0]
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_tokens(rng_key):
    cfg = tiny("deepseek-moe-16b")
    p = MOE.moe_init(rng_key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y_small, _ = MOE.moe_apply(p, x, cfg, capacity_factor=0.25)
    y_big, _ = MOE.moe_apply(p, x, cfg, capacity_factor=16.0)
    # with tight capacity some token outputs must differ (drops occurred)
    assert not np.allclose(np.asarray(y_small), np.asarray(y_big))
    assert np.all(np.isfinite(np.asarray(y_small)))
