"""The plain references against the program, and the control against the
limit, at a size a test run holds.

The program in float32 agrees with the float32 reference to rounding; the
control, the same reference at float8, puts first tokens whose reference
logit lies below the best by more than the tiny configurations' limit."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import check, registry, testing  # noqa: E402
from chipbench.cell import model_config  # noqa: E402

FAMILIES = testing.TINY_CONFIGS


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = str(tmp_path_factory.mktemp("tiny"))
    testing.tiny_root(r)
    return r


def _setup(root, name, seed):
    import jax
    cfg = registry.config(name, root)
    ref = registry.reference(cfg["reference"], root)
    p = cfg["program"]
    params = jax.jit(lambda k: ref.make_params(p, k))(
        jax.random.PRNGKey(seed))
    toks = np.random.default_rng(seed).integers(0, p["vocab"], (1, 48),
                                                dtype=np.int32)
    return cfg, ref, p, params, toks


@pytest.mark.parametrize("name", FAMILIES)
def test_reference_agrees_with_the_program(root, name):
    import jax
    from repro.configs.base import DEFAULT_TUNABLES
    from repro.models import model as M
    cfg, ref, p, params, toks = _setup(root, name, 0)
    mcfg = model_config(p)
    with jax.default_matmul_precision("highest"):
        got = M.forward(params, mcfg, {"tokens": toks}, DEFAULT_TUNABLES)[0]
    pos = np.arange(48, dtype=np.int32)[None]
    want = check.reference_logits(ref, params, p, [toks[0]], [pos[0]], 48)[0]
    np.testing.assert_allclose(np.asarray(got[0, :, :p["vocab"]]), want,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", FAMILIES)
def test_control_fails_the_limit(root, name, seed):
    cfg, ref, p, params, toks = _setup(root, name, seed)
    pos = [np.arange(47, dtype=np.int32)]
    exact = check.reference_logits(ref, params, p, [toks[0]], pos, 47)
    low = check.reference_logits(ref, params, p, [toks[0]], pos, 47,
                                 precision="float8")
    served = [e.argmax(-1) for e in exact]
    assert check.logit_gap(exact, served) == 0.0
    gap = check.control_gap(exact, low)
    assert gap > cfg["check"]["logit_gap"]
    # judged as a run is, the control is not correct
    checks, ok = check.verdict(cfg["check"], {"logit_gap": gap})
    assert ok is False and checks["logit_gap"]["value"] == gap
