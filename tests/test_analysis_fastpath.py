"""Parity + behaviour tests for the compiled analysis fast path.

The streaming DBSCAN (fused neighbour kernel + pointer-jumping label
propagation) must be *bit-identical* to the dense one-hop oracle
(``impl="ref"``, the seed formulation); the jitted forest/LSTM training must
match their eager twins; the Explorer memo must be bounded and clearable.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.dbscan import (agglomerative_single_link, dbscan,
                               pairwise_sq_dists)
from repro.core.explorer import Explorer
from repro.core.forest import ForestConfig, RandomForest
from repro.core.lstm import PredictorConfig, WorkloadPredictor
from repro.kernels import dispatch
from repro.kernels.pairdist import (neighbor_adjacency, neighbor_count,
                                    ref_adjacency, ref_neighbor_count,
                                    unpack_bits)


def _blobs(n, f, seed, spread=0.5, shift=3.0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32) * spread
    x[: n // 2] += shift
    x[n // 4: n // 2] -= 2 * shift
    return x


# -- fused neighbour kernel ---------------------------------------------------


@pytest.mark.parametrize("n,f", [(64, 8), (130, 4), (257, 16), (2048, 16)])
def test_neighbor_count_matches_ref(n, f):
    x = _blobs(n, f, seed=n)
    eps = 1.5
    got = np.asarray(neighbor_count(jnp.asarray(x), eps))
    want = np.asarray(ref_neighbor_count(jnp.asarray(x), eps))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,f", [(96, 8), (200, 16)])
def test_packed_adjacency_matches_ref(n, f):
    x = _blobs(n, f, seed=7 * n)
    eps = 1.2
    _, packed = neighbor_adjacency(jnp.asarray(x), eps)
    got = np.asarray(unpack_bits(packed))[:n, :n]
    want = np.asarray(ref_adjacency(jnp.asarray(x), eps))
    np.testing.assert_array_equal(got, want)


def test_pallas_interpret_matches_xla_twin():
    x = _blobs(150, 8, seed=3)
    c1, p1 = neighbor_adjacency(jnp.asarray(x), 1.0, impl="pallas_interpret")
    c2, p2 = neighbor_adjacency(jnp.asarray(x), 1.0, impl="xla")
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def test_dbscan_odd_block_size():
    # block sizes are rounded to the kernel's bit-pack granularity (8)
    x = _blobs(200, 4, seed=9)
    got = dbscan(x, eps=0.9, min_pts=4, block=100)
    want = dbscan(x, eps=0.9, min_pts=4, impl="ref")
    np.testing.assert_array_equal(got, want)


def test_column_tiled_kernel_matches_xla_twin():
    # past 1024 columns the kernel walks lane-dense column tiles and pads
    # to a multiple of 1024; the twin must pad and pack identically
    import repro.kernels.pairdist as P
    x = _blobs(1100, 4, seed=13)
    c1, p1 = P._neighbor_adjacency_pallas(jnp.asarray(x), eps_sq=0.81,
                                          block=128, interpret=True)
    c2, p2 = neighbor_adjacency(jnp.asarray(x), 0.9, block=128, impl="xla")
    assert p1.shape == (2048, 256)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def test_dispatch_interpret_never_implicit():
    # CPU resolves to the XLA tiles, a TPU to compiled Pallas; interpret
    # mode only on explicit request
    assert dispatch.resolve("auto") in ("pallas", "xla")
    assert dispatch.resolve("pallas_interpret") == "pallas_interpret"
    assert dispatch.interpret_mode("pallas_interpret")
    with pytest.raises(ValueError):
        dispatch.resolve("nope")
    if dispatch.backend() != "tpu":
        # compiled Pallas off a TPU is an error, never a quiet downgrade
        with pytest.raises(RuntimeError, match="needs a TPU"):
            dispatch.resolve("pallas")
        with pytest.raises(RuntimeError, match="only has a Pallas kernel"):
            dispatch.interpret_mode()


# -- streaming DBSCAN vs dense oracle -----------------------------------------


@pytest.mark.parametrize("n", [50, 130, 512, 2048])
@pytest.mark.parametrize("min_pts", [1, 4, 8])
def test_dbscan_bitwise_parity_with_oracle(n, min_pts):
    x = _blobs(n, 8, seed=n + min_pts)
    got = dbscan(x, eps=0.9, min_pts=min_pts)
    want = dbscan(x, eps=0.9, min_pts=min_pts, impl="ref")
    np.testing.assert_array_equal(got, want)


def test_dbscan_parity_with_noise():
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(0, .05, (60, 4)),
                        rng.normal(5, .05, (60, 4)),
                        rng.uniform(-10, 10, (8, 4))]).astype(np.float32)
    got = dbscan(x, eps=0.5, min_pts=4)
    want = dbscan(x, eps=0.5, min_pts=4, impl="ref")
    np.testing.assert_array_equal(got, want)
    assert (got == -1).sum() >= 3


def test_pointer_jumping_equals_seed_propagation_on_chain():
    # worst case for one-hop propagation: a chain with diameter N
    n = 600
    x = np.zeros((n, 2), np.float32)
    x[:, 0] = np.arange(n) * 0.9
    fast = dbscan(x, eps=1.0, min_pts=2)
    seed = dbscan(x, eps=1.0, min_pts=2, impl="ref")
    np.testing.assert_array_equal(fast, seed)
    assert fast.max() == 0          # a single cluster spanning the chain


def test_single_link_matches_seed_numpy_loop():
    x = _blobs(300, 4, seed=11)

    def seed_single_link(x, thresh):     # the seed implementation, verbatim
        d2 = np.asarray(pairwise_sq_dists(jnp.asarray(x), impl="xla"))
        adj = d2 <= thresh ** 2
        n = adj.shape[0]
        labels = np.arange(n)
        changed = True
        while changed:
            nbr_min = np.where(adj, labels[None, :], n).min(1)
            new = np.minimum(labels, nbr_min)
            changed = bool((new != labels).any())
            labels = new
        out = np.full(n, -1, np.int64)
        for i, u in enumerate(np.unique(labels)):
            out[labels == u] = i
        return out

    np.testing.assert_array_equal(agglomerative_single_link(x, 0.5),
                                  seed_single_link(x, 0.5))


# -- jitted training vs eager twins -------------------------------------------


def test_forest_compiled_agrees_with_seed_eager():
    rng = np.random.default_rng(0)
    X = np.concatenate([rng.normal(0, 1, (200, 8)),
                        rng.normal(3, 1, (200, 8))]).astype(np.float32)
    y = np.concatenate([np.zeros(200, np.int64), np.ones(200, np.int64)])
    fc = ForestConfig(n_trees=8, depth=5, n_classes=2)
    fast = RandomForest(fc).fit(X, y, seed=3)
    seed = RandomForest(fc).fit(X, y, seed=3, compiled=False)
    # same bootstrap draws + same split algorithm -> same predictions
    np.testing.assert_array_equal(fast.predict(X), seed.predict(X))


def test_forest_jit_cache_shared_across_instances():
    from repro.core.forest import _fit_forest
    rng = np.random.default_rng(1)
    X = rng.normal(size=(100, 4)).astype(np.float32)
    y = rng.integers(0, 3, 100)
    fc = ForestConfig(n_trees=4, depth=3, n_classes=3)
    RandomForest(fc).fit(X, y)
    misses = _fit_forest._cache_size()
    RandomForest(fc).fit(X, y)      # second instance, same shapes + config
    assert _fit_forest._cache_size() == misses


def test_forest_max_samples_subsampling():
    rng = np.random.default_rng(2)
    X = np.concatenate([rng.normal(0, .5, (300, 6)),
                        rng.normal(4, .5, (300, 6))]).astype(np.float32)
    y = np.concatenate([np.zeros(300, np.int64), np.ones(300, np.int64)])
    fc = ForestConfig(n_trees=8, depth=4, n_classes=2, max_samples=128)
    rf = RandomForest(fc).fit(X, y)
    assert rf.score(X, y) >= 0.95


def test_predictor_compiled_matches_python_loop():
    seq = np.array([0, 1, 2, 3] * 40)
    pc = PredictorConfig(n_classes=4, hidden=16, window=6, epochs=25)
    fast = WorkloadPredictor(pc).fit(seq, seed=5)
    slow = WorkloadPredictor(pc).fit(seq, seed=5, compiled=False)
    # identical RNG chain and batch slicing; jit-vs-eager float drift only
    for k in ("wx", "wh", "b"):
        np.testing.assert_allclose(np.asarray(fast.params[k]),
                                   np.asarray(slow.params[k]),
                                   rtol=2e-3, atol=2e-4)
    s = fast.score(seq)
    assert all(v >= 0.85 for v in s.values()), s


def test_predictor_early_stop_converges_and_is_accurate():
    seq = np.array([0, 1, 2] * 80)
    pc = PredictorConfig(n_classes=3, hidden=32, window=6, epochs=60,
                         batch=64, early_stop_tol=1e-2, patience=2,
                         target_loss=0.1)
    p = WorkloadPredictor(pc).fit(seq)
    s = p.score(seq)
    assert all(v >= 0.9 for v in s.values()), s


# -- Explorer memo bounding ---------------------------------------------------


def test_explorer_memo_bounded_and_clearable():
    from repro.configs.base import DEFAULT_TUNABLES
    space = {"microbatches": [1, 2, 4, 8], "prefetch": [1, 2, 4]}
    ex = Explorer(space, max_memo=4)
    ex.global_search(lambda t: float(t.microbatches), DEFAULT_TUNABLES)
    assert ex.memo_size() <= 4
    ex.clear()
    assert ex.memo_size() == 0
    # after clear, evaluations are re-measured (no stale cross-workload reuse)
    res = ex.global_search(lambda t: float(t.prefetch), DEFAULT_TUNABLES)
    assert res.evaluations > 0


def test_plugin_clears_memo_on_label_change(tmp_path):
    from repro.configs.base import DEFAULT_TUNABLES
    from repro.core.knowledge import WorkloadDB
    from repro.core.monitor import KermitMonitor
    from repro.core.plugin import KermitPlugin
    import time as _time

    db = WorkloadDB(tmp_path)
    mon = KermitMonitor(window_size=4)
    ex = Explorer({"microbatches": [1, 2, 4]})
    plug = KermitPlugin(db, mon, ex, DEFAULT_TUNABLES)

    lbl_a = db.insert({"mean": np.zeros(4), "std": np.ones(4), "n": 16})
    lbl_b = db.insert({"mean": np.ones(4) * 9, "std": np.ones(4), "n": 16})

    class Ctx:                       # minimal stand-in for WorkloadContext
        def __init__(self, label):
            self.window_id = mon.windows_emitted   # fresh w.r.t. staleness
            self.timestamp = _time.time()
            self.current_label = label

    costs = {lbl_a: 1.0, lbl_b: 2.0}
    current = {"label": lbl_a}
    mon.latest_context = lambda: Ctx(current["label"])

    def objective(t):
        return costs[current["label"]] + t.microbatches * 0.01

    plug.on_resource_request(objective)
    assert ex.memo_size() > 0
    db.get(lbl_a).has_optimal = False        # force a re-search next time
    current["label"] = lbl_b
    plug.on_resource_request(objective)
    # the memo now belongs to workload B: no workload-A costs survive
    assert all(abs(v - 2.0) < 1.0 for v in ex._memo.values())
