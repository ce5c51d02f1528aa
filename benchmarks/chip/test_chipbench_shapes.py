"""Operations and bytes from shapes: each family's counts file against
counts made by hand at a tiny size, every configuration's counts against
the size of the weights its reference draws and against values pinned
when the counts moved into their family files, and the dispatch by family
to ``counts/<family>.py``."""
import os
import shutil
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import registry, shapes as S, testing  # noqa: E402

QWEN = {"family": "dense", "n_layers": 2, "d_model": 8, "n_heads": 2,
        "n_kv_heads": 1, "d_ff": 16, "vocab": 10, "qkv_bias": True,
        "tie_embeddings": True, "dtype": "bfloat16", "rope_theta": 1e4}
MAMBA = {"family": "ssm", "n_layers": 1, "d_model": 4, "vocab": 10,
         "dtype": "bfloat16", "n_heads": 0, "n_kv_heads": 0, "d_ff": 0,
         "ssm": {"d_state": 3, "d_conv": 2, "expand": 2, "head_dim": 4,
                 "n_groups": 1, "chunk": 2}}
CONFIGS = registry.config_names()


def test_attention_family_by_hand():
    dense = registry.counts("dense")
    # per layer: q 8x8, k and v 8x4 each, o 8x8, MLP three 8x16
    assert dense.layer_matmul_params(QWEN) == 64 + 64 + 64 + 384
    # 3 tokens x 2 layers x 2 x 576, causal attention 4*L*H*hd*(1+2+3),
    # and the head at the last position 2*8*10
    assert dense.prefill_flops(QWEN, 3) == 6912 + 384 + 160
    assert dense.decode_flops(QWEN, 5) == 2304 + 160 + 4 * 2 * 2 * 4 * 5
    # embedding padded to 256 rows, final norm, 2 x (576 + 2 norms + bias)
    assert dense.weight_bytes(QWEN) == 2 * (256 * 8 + 8 + 2 * (576 + 16
                                                                + 16))
    # keys and values of 5 positions plus the new pair, 2 layers, bf16
    assert dense.decode_row_bytes(QWEN, 5) == 32 * 5 + 32
    # shapes dispatches to the family file
    for f in ("weight_bytes", "prefill_flops", "decode_flops",
              "decode_row_bytes"):
        args = (QWEN,) if f == "weight_bytes" else (QWEN, 5)
        assert getattr(S, f)(*args) == getattr(dense, f)(*args)


def test_state_space_family_by_hand():
    ssm = registry.counts("ssm")
    # d_inner 8, 2 heads, conv over 8 + 2*3 = 14 channels
    assert ssm.layer_matmul_params(MAMBA) == 4 * (16 + 6 + 2) + 8 * 4
    per_token = 2 * 128 + (5 * 2 * 3 * 4 + 2 * 2 * 14)
    assert ssm.prefill_flops(MAMBA, 2) == 2 * per_token + 2 * 4 * 10
    assert ssm.decode_flops(MAMBA, 7) == per_token + 2 * 4 * 10
    assert ssm.weight_bytes(MAMBA) == 2 * (256 * 4 + 4 + 128 + 4 + 28 + 14
                                           + 8) + 4 * 3 * 2
    # f32 state 2x3x4 and a conv window of 1 x 14 bf16, read and written
    assert ssm.decode_row_bytes(MAMBA, 9) == 2 * (4 * 24 + 2 * 14)
    for f in ("weight_bytes", "prefill_flops", "decode_flops",
              "decode_row_bytes"):
        args = (MAMBA,) if f == "weight_bytes" else (MAMBA, 9)
        assert getattr(S, f)(*args) == getattr(ssm, f)(*args)


# The hand-made blocks, then every configuration file's program block at
# its size and cut by its tiny block
BLOCKS = [("qwen2", "qwen2", QWEN), ("mamba2", "mamba2", MAMBA)] + [
    (c["name"], c["reference"], c["program"])
    for n in CONFIGS for c in (registry.config(n),
                               testing.tiny_config(registry.config(n)))]


@pytest.mark.parametrize("ref, p", [b[1:] for b in BLOCKS],
                         ids=[b[0] for b in BLOCKS])
def test_weight_bytes_match_the_reference_weights(ref, p):
    import jax
    ref = registry.reference(ref)
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


@pytest.mark.parametrize("name", CONFIGS)
def test_published_sizes(name):
    """Each configuration's weights against the size its file states as
    published (``published_weight_bytes``)."""
    cfg = registry.config(name)
    want = cfg["published_weight_bytes"]
    assert abs(S.weight_bytes(cfg["program"]) - want) / want < 0.01


# The counts of the two configurations' program blocks as they were before
# each family's counts moved into a file of its own: prompt lengths 1, 256
# and 2048; contexts 1, 700 and 2560.
PINNED = {
    "qwen2-1.5b": {
        "weight_bytes": 3087821824,
        "prefill_flops": [3087310848.0, 676946116608.0, 5727981797376.0],
        "decode_flops": [3087310848.0, 3207561216.0, 3527540736.0],
        "decode_row_bytes": [57344.0, 20099072.0, 73428992.0],
        "decode_step_bytes": 3181407232.0},
    "mamba2-1.3b": {
        "weight_bytes": 2688122880,
        "prefill_flops": [2812268544.0, 667427426304.0, 5337977868288.0],
        "decode_flops": [2812268544.0, 2812268544.0, 2812268544.0],
        "decode_row_bytes": [203833344.0, 203833344.0, 203833344.0],
        "decode_step_bytes": 3299622912.0},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_are_pinned(name):
    p = registry.config(name)["program"]
    want = PINNED[name]
    prompts, ctx = np.array([1, 256, 2048]), np.array([1, 700, 2560])
    assert S.weight_bytes(p) == want["weight_bytes"]
    assert S.prefill_flops(p, prompts).tolist() == want["prefill_flops"]
    assert S.decode_flops(p, ctx).tolist() == want["decode_flops"]
    assert S.decode_row_bytes(p, ctx).tolist() == want["decode_row_bytes"]
    assert [float(S.prefill_flops(p, int(s))) for s in prompts] == \
        want["prefill_flops"]
    assert [float(S.decode_flops(p, int(c))) for c in ctx] == \
        want["decode_flops"]
    assert S.decode_step_bytes(p, ctx) == want["decode_step_bytes"]


def test_a_family_without_counts_raises():
    p = dict(QWEN, family="hybrid")
    for f in (S.weight_bytes, lambda p: S.prefill_flops(p, 3),
              lambda p: S.decode_flops(p, 3),
              lambda p: S.decode_row_bytes(p, 3)):
        with pytest.raises(FileNotFoundError,
                           match=r"'hybrid'.*counts/hybrid\.py"):
            f(p)


def test_a_new_family_is_a_new_counts_file(tmp_path):
    """A counts file added under a new family name is dispatched to, in the
    folder that ``shapes.at`` binds, and nowhere else."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(HERE, "counts"),
                    os.path.join(root, "counts"))
    with open(os.path.join(root, "counts", "toy.py"), "w") as f:
        f.write("def weight_bytes(p):\n    return 7 * p['n_layers']\n"
                "def prefill_flops(p, s):\n    return 3.0 * s\n"
                "def decode_flops(p, ctx):\n    return 5.0 + ctx\n"
                "def decode_row_bytes(p, ctx):\n    return 2.0 * ctx\n")
    p = {"family": "toy", "n_layers": 4}
    shapes = S.at(root)
    assert shapes.weight_bytes(p) == 28
    assert shapes.prefill_flops(p, 2) == 6.0
    assert shapes.decode_step_bytes(p, np.array([1, 2])) == 28 + 6.0
    call = SimpleNamespace(requests=np.array([0]), steps=1)
    assert shapes.decode_steps(p, [call], np.array([3]),
                               np.array([1])).tolist() == [[9.0, 36.0]]
    # the dense family there is the same file as here
    q = registry.config("qwen2-1.5b")["program"]
    assert shapes.weight_bytes(q) == S.weight_bytes(q)
    with pytest.raises(FileNotFoundError, match="'toy'"):
        S.weight_bytes(p)
