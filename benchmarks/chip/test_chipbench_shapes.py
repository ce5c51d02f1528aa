"""Operations and bytes from shapes, against counts made by hand at a tiny
size, and against the size of the weights the reference draws."""
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import registry, shapes as S  # noqa: E402

QWEN = {"family": "dense", "n_layers": 2, "d_model": 8, "n_heads": 2,
        "n_kv_heads": 1, "d_ff": 16, "vocab": 10, "qkv_bias": True,
        "tie_embeddings": True, "dtype": "bfloat16", "rope_theta": 1e4}
MAMBA = {"family": "ssm", "n_layers": 1, "d_model": 4, "vocab": 10,
         "dtype": "bfloat16", "n_heads": 0, "n_kv_heads": 0, "d_ff": 0,
         "ssm": {"d_state": 3, "d_conv": 2, "expand": 2, "head_dim": 4,
                 "n_groups": 1, "chunk": 2}}


def test_attention_family_by_hand():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, MLP three 8x16
    assert S.layer_matmul_params(QWEN) == 64 + 64 + 64 + 384
    # 3 tokens x 2 layers x 2 x 576, causal attention 4*L*H*hd*(1+2+3),
    # and the head at the last position 2*8*10
    assert S.prefill_flops(QWEN, 3) == 6912 + 384 + 160
    assert S.decode_flops(QWEN, 5) == 2304 + 160 + 4 * 2 * 2 * 4 * 5
    # embedding padded to 256 rows, final norm, 2 x (576 + 2 norms + bias)
    assert S.weight_bytes(QWEN) == 2 * (256 * 8 + 8 + 2 * (576 + 16 + 16))
    # keys and values of 5 positions plus the new pair, 2 layers, bf16
    assert S.decode_row_bytes(QWEN, 5) == 32 * 5 + 32


def test_state_space_family_by_hand():
    # d_inner 8, 2 heads, conv over 8 + 2*3 = 14 channels
    assert S.layer_matmul_params(MAMBA) == 4 * (16 + 6 + 2) + 8 * 4
    per_token = 2 * 128 + (5 * 2 * 3 * 4 + 2 * 2 * 14)
    assert S.prefill_flops(MAMBA, 2) == 2 * per_token + 2 * 4 * 10
    assert S.decode_flops(MAMBA, 7) == per_token + 2 * 4 * 10
    assert S.weight_bytes(MAMBA) == 2 * (256 * 4 + 4 + 128 + 4 + 28 + 14
                                         + 8) + 4 * 3 * 2
    # f32 state 2x3x4 and a conv window of 1 x 14 bf16, read and written
    assert S.decode_row_bytes(MAMBA, 9) == 2 * (4 * 24 + 2 * 14)


@pytest.mark.parametrize("p", [QWEN, MAMBA], ids=["qwen2", "mamba2"])
def test_weight_bytes_match_the_reference_weights(p):
    import jax
    ref = registry.reference("qwen2" if p is QWEN else "mamba2")
    tree = jax.eval_shape(lambda: ref.make_params(p, jax.random.PRNGKey(0)))
    assert S.weight_bytes(p) == sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def test_decode_steps_count_live_rows_only():
    call = SimpleNamespace(requests=np.array([0, 1]), steps=2)
    need = S.decode_steps(QWEN, [call], np.array([3, 5]), np.array([2, 1]))
    assert need.shape == (2, 2)
    assert need[0, 0] == S.decode_flops(QWEN, 4) + S.decode_flops(QWEN, 6)
    assert need[1, 0] == S.decode_flops(QWEN, 5)
    assert need[1, 1] == S.weight_bytes(QWEN) + S.decode_row_bytes(QWEN, 5)


@pytest.mark.parametrize("name", ["qwen2-1.5b", "mamba2-1.3b"])
def test_published_sizes(name):
    """The configurations' weights: 3.09 GB and 2.69 GB in bf16."""
    p = registry.config(name)["program"]
    want = {"qwen2-1.5b": 3.09e9, "mamba2-1.3b": 2.69e9}[name]
    assert abs(S.weight_bytes(p) - want) / want < 0.01
