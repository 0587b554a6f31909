"""GPipe pipeline == sequential execution (4 stages, subprocess with 4
fake devices since jax locks the device count at first init)."""
import os
import subprocess
import sys

import pytest

from repro.distributed.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(1, 4) == 0.75
    assert abs(bubble_fraction(16, 4) - 3 / 19) < 1e-12
    assert bubble_fraction(100, 2) < 0.01


@pytest.mark.multidevice
def test_gpipe_matches_sequential_subprocess():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.distributed.pipeline import gpipe_forward
from repro.launch.mesh import make_mesh

S, M, B, D = 4, 6, 2, 8
mesh = make_mesh((S,), ("stage",))
key = jax.random.PRNGKey(0)
W = jax.random.normal(key, (S, D, D)) * 0.3          # one matmul per stage

def stage_fn(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.PRNGKey(1), (M, B, D))
got = gpipe_forward(stage_fn, W, x, mesh=mesh, axis_name="stage")
# sequential reference
want = x
for s in range(S):
    want = jnp.tanh(want @ W[s])
np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
print("PIPE_OK")
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env={**os.environ, "PYTHONPATH": "src"}, cwd=root)
    assert "PIPE_OK" in out.stdout, out.stderr[-2000:]
