"""GPipe pipeline parallelism: output must equal the sequential layer stack.
Runs in a subprocess with an 8-device host platform (the main test process
must keep seeing 1 device)."""
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax import lax
from repro.train.pipeline import gpipe_apply, stage_split

L, D, B = 8, 16, 12
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (L, D, D)) * (D ** -0.5)
x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

def layer(w, x):
    return jnp.tanh(x @ w)

def stage_fn(p_stage, x):          # p_stage: (L/S, D, D)
    def body(x, w):
        return layer(w, x), None
    y, _ = lax.scan(body, x, p_stage)
    return y

# sequential reference
ref = x
for i in range(L):
    ref = layer(ws[i], ref)

mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(jax.sharding.AxisType.Auto,))
staged = stage_split({"w": ws}, 4)
out = gpipe_apply(staged["w"], x, stage_fn, mesh=mesh, n_microbatches=4)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print("PIPELINE_OK")
"""


def test_gpipe_matches_sequential():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin",
                            "HOME": "/root",
                            # hosts with an accelerator plugin installed probe
                            # device metadata at import; this test's 8 devices
                            # are forced host-platform ones
                            "JAX_PLATFORMS": "cpu"})
    assert "PIPELINE_OK" in r.stdout, f"\nstdout:{r.stdout}\nstderr:{r.stderr[-2000:]}"
