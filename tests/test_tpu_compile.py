"""Main-path kernels and the qwen2-1.5b decode step compile for a described
TPU v5e chip, at real widths, with no chip attached.

The TPU compiler refuses what interpret mode accepts: unaligned tiles, too
much VMEM, in-kernel reshapes Mosaic cannot lower.  These compiles catch
that on the CPU.  A compile that passes is not a chip run.  The topology is
described inside a fixture (never at import), and the kernels are called
with ``interpret=False`` here because ``jax.default_backend()`` is the CPU.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import DEFAULT_TUNABLES, ShapeSpec
from repro.configs.registry import get_config
from repro.kernels import flash_attention as FA
from repro.kernels import pairdist as PD
from repro.kernels import ssd_scan as SS
from repro.models import model as M
from repro.train.step import make_serve_step

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described device is written but can never be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_forward_compiles_at_qwen2_widths(one_chip):
    cfg = get_config("qwen2-1.5b")
    B, S = 8, 512
    q = _sds(one_chip, (B, S, cfg.n_heads, cfg.hd), cfg.dtype)
    kv = _sds(one_chip, (B, S, cfg.n_kv_heads, cfg.hd), cfg.dtype)
    compiled = FA._flash_fwd.lower(q, kv, kv, interpret=False).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("n", [64, 2048])
def test_neighbor_adjacency_compiles(one_chip, n):
    x = _sds(one_chip, (n, 16), "float32")
    compiled = PD._neighbor_adjacency_pallas.lower(
        x, eps_sq=0.81, block=128, interpret=False).compile()
    assert _has_kernel(compiled)


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-1.3b")
    s = cfg.ssm
    B, S = 2, 2 * s.chunk
    H = s.expand * cfg.d_model // s.head_dim
    args = (_sds(one_chip, (B, S, H, s.head_dim), cfg.dtype),
            _sds(one_chip, (B, S, H), "float32"),
            _sds(one_chip, (H,), "float32"),
            _sds(one_chip, (B, S, s.n_groups, s.d_state), cfg.dtype),
            _sds(one_chip, (B, S, s.n_groups, s.d_state), cfg.dtype))
    compiled = SS._ssd_fwd.lower(*args, chunk=s.chunk,
                                 interpret=False).compile()
    assert _has_kernel(compiled)


def test_qwen2_decode_step_compiles_at_full_width(one_chip):
    cfg = get_config("qwen2-1.5b")
    shape = ShapeSpec("decode", 128, 8, "decode")

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0),
                                                   cfg)))
    cache = on_chip(M.cache_specs(cfg, shape))
    batch = on_chip(M.input_specs(cfg, shape))
    step = jax.jit(make_serve_step(cfg, DEFAULT_TUNABLES),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache, batch).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES


def test_qwen2_decode_step_writes_the_cache_in_place(one_chip):
    """At the benchmark cell's largest capacity the donated cache is the
    program's output buffer, and the program holds no scratch the size of
    one layer's keys and values: a relayout or a copy of the cache through
    the layer loop would need at least that."""
    cfg = get_config("qwen2-1.5b")

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(lambda: M.init(jax.random.PRNGKey(0),
                                                   cfg)))
    cache = on_chip(jax.eval_shape(lambda: M.init_cache(cfg, 8, 2560)))
    batch = on_chip(M.input_specs(cfg, ShapeSpec("decode", 2560, 8,
                                                 "decode")))
    step = jax.jit(make_serve_step(cfg, DEFAULT_TUNABLES),
                   donate_argnums=(1,))
    mem = step.lower(params, cache, batch).compile().memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // cfg.n_layers
