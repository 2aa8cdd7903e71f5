"""Pipeline parallelism: GPipe schedule == sequential stage application."""
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_pipeline_matches_sequential():
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {SRC!r})
import jax, jax.numpy as jnp, numpy as np
from repro.compat import make_mesh
from repro.launch.pp import pipeline_apply

mesh = make_mesh((4,), ("model",))
rng = np.random.default_rng(0)
S, B, D = 4, 8, 16
w = jnp.asarray(rng.standard_normal((S, D, D)) / np.sqrt(D), jnp.float32)
b = jnp.asarray(rng.standard_normal((S, D)) * 0.1, jnp.float32)
x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)

def stage_fn(p, xm):
    return jnp.tanh(xm @ p["w"] + p["b"])

y_pp = pipeline_apply({{"w": w, "b": b}}, x, stage_fn, mesh,
                      microbatches=4)
y_ref = x
for s in range(S):
    y_ref = jnp.tanh(y_ref @ w[s] + b[s])
err = float(jnp.abs(y_pp - y_ref).max())
assert err < 1e-5, err
print("pipeline OK", err)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    assert "pipeline OK" in r.stdout
