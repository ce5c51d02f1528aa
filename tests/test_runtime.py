"""Runtime substrate: checkpointing, pipeline determinism, failure recovery,
straggler detection, elastic restore."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DEFAULT_TUNABLES, ShapeSpec
from repro.data.pipeline import TokenPipeline
from repro.optim.adamw import OptConfig
from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.fault import FailureInjector, StragglerDetector
from repro.runtime.loop import Trainer
from repro.train.step import init_train_state
from tests.conftest import tiny

CFG = tiny("qwen2-1.5b")
SHAPE = ShapeSpec("t", 64, 4, "train")
OC = OptConfig(lr=1e-3, warmup=2)


def test_checkpoint_roundtrip_bitwise(tmp_path, rng_key):
    state = init_train_state(rng_key, CFG, OC, DEFAULT_TUNABLES)
    mgr = CheckpointManager(tmp_path, keep=2)
    mgr.save(7, state, {"pipeline": {"seed": 0, "step": 7}})
    template = jax.eval_shape(
        lambda: init_train_state(rng_key, CFG, OC, DEFAULT_TUNABLES))
    restored, meta = mgr.restore(template)
    assert meta["step"] == 7
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_k_gc(tmp_path, rng_key):
    state = init_train_state(rng_key, CFG, OC, DEFAULT_TUNABLES)
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, state)
    assert mgr.steps() == [3, 4]


def test_pipeline_deterministic_and_resumable():
    p1 = TokenPipeline(CFG, SHAPE, seed=5)
    batches = [p1.next() for _ in range(4)]
    st = p1.state()
    nxt = p1.next()
    p1.close()
    p2 = TokenPipeline.restore(CFG, SHAPE, st)
    nxt2 = p2.next()
    p2.close()
    np.testing.assert_array_equal(np.asarray(nxt["tokens"]),
                                  np.asarray(nxt2["tokens"]))
    # restart from scratch reproduces the whole stream
    p3 = TokenPipeline(CFG, SHAPE, seed=5)
    again = [p3.next() for _ in range(4)]
    p3.close()
    for a, b in zip(batches, again):
        np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                      np.asarray(b["tokens"]))


def test_failure_recovery_equals_uninterrupted_run(tmp_path):
    """Crash + restore + replay must land on the SAME trajectory as a run
    with no failure (exact recovery, not approximate)."""
    t1 = Trainer(CFG, SHAPE, OC, DEFAULT_TUNABLES, ckpt_dir=tmp_path / "a",
                 ckpt_every=4, seed=3)
    r1 = t1.run(12)
    t2 = Trainer(CFG, SHAPE, OC, DEFAULT_TUNABLES, ckpt_dir=tmp_path / "b",
                 ckpt_every=4, seed=3,
                 injector=FailureInjector(fail_steps=(6,)))
    r2 = t2.run(12)
    assert r2.failures_recovered == 1
    np.testing.assert_allclose(r1.losses[-1], r2.losses[-1], rtol=1e-5)


def test_straggler_detector_spike_and_sustained():
    det = StragglerDetector(window=8, spike_factor=3.0)
    for i in range(40):
        det.observe(i, 0.10 + 0.001 * (i % 3))
    ev = det.observe(40, 0.50)
    assert ev and ev["kind"] == "spike"
    for i in range(41, 80):
        det.observe(i, 0.30 + 0.001 * (i % 3))
    kinds = {e["kind"] for e in det.events}
    assert "sustained" in kinds


def test_elastic_restore_roundtrip(tmp_path, rng_key):
    """Checkpoint written without a mesh restores onto a (degenerate) mesh
    with shardings applied — the elastic re-mesh path."""
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.fault import elastic_restore
    from repro.sharding import rules

    state = init_train_state(rng_key, CFG, OC, DEFAULT_TUNABLES)
    mgr = CheckpointManager(tmp_path)
    mgr.save(3, state)
    template = jax.eval_shape(
        lambda: init_train_state(rng_key, CFG, OC, DEFAULT_TUNABLES))
    mesh = make_host_mesh()
    axes = rules.state_axes_tree(template)
    restored, meta = elastic_restore(mgr, template, mesh, axes)
    rules.set_mesh(None)
    assert meta["step"] == 3
    l0 = jax.tree_util.tree_leaves(restored)[0]
    assert hasattr(l0, "sharding")
    np.testing.assert_array_equal(
        np.asarray(jax.tree_util.tree_leaves(state)[0]), np.asarray(l0))


@pytest.mark.parametrize("error,infeasible", [
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm", True),
    ("INVALID_ARGUMENT: Mosaic failed to compile TPU kernel", False),
])
def test_measured_objective_only_oom_is_infeasible(monkeypatch, error,
                                                   infeasible):
    import repro.runtime.loop as loop

    tr = Trainer(CFG, SHAPE, OC, DEFAULT_TUNABLES, seed=0)

    def failing_step(*_):
        def step(state, batch):
            raise jax.errors.JaxRuntimeError(error)
        return step

    monkeypatch.setattr(loop, "make_train_step", failing_step)
    objective = tr.measured_objective()
    try:
        if infeasible:
            assert objective(DEFAULT_TUNABLES) == float("inf")
            assert tr.infeasible == 1
        else:
            with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
                objective(DEFAULT_TUNABLES)
            assert tr.infeasible == 0
    finally:
        tr.pipeline.close()


def test_peak_flops_table_keyed_by_device_kind():
    from repro.runtime.telemetry import PEAK_FLOPS, TelemetryEmitter, peak_flops

    assert peak_flops("TPU v5 lite") == 197e12
    assert peak_flops("cpu") == 2e11
    with pytest.raises(KeyError, match="no peak FLOP/s"):
        peak_flops("TPU v0 imaginary")
    kind = jax.devices()[0].device_kind
    assert TelemetryEmitter(seq_len=8, global_batch=1).peak == PEAK_FLOPS[kind]


def test_compile_cache_dir_env_wins_else_fixed_in_checkout(monkeypatch):
    from pathlib import Path
    from repro.runtime import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        repo = Path(__file__).resolve().parents[1]
        assert got == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
