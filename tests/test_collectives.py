"""Ring collective patterns vs jax built-ins (on however many host
devices exist; the ring logic is device-count generic)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.collectives import (
    make_ring_all_gather,
    reduce_scatter_then_gather,
    ring_all_gather,
)
from repro.launch.mesh import make_host_mesh, make_mesh


def test_ring_all_gather_matches_all_gather():
    n = len(jax.devices())
    mesh = make_mesh((n,), ("x",))
    x = jnp.arange(n * 4 * 3, dtype=jnp.float32).reshape(n * 4, 3)
    got = make_ring_all_gather(mesh, "x")(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


@pytest.mark.multidevice
def test_ring_all_gather_8_devices_subprocess():
    """Real multi-device ring semantics (8 fake CPU devices; jax locks the
    device count at first init, so this needs a fresh process)."""
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.distributed.collectives import make_ring_all_gather
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("x",))
x = jnp.arange(8 * 2 * 3, dtype=jnp.float32).reshape(16, 3)
got = make_ring_all_gather(mesh, "x")(x)
np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
print("RING_OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"},
                         cwd=__import__("os").path.dirname(
                             __import__("os").path.dirname(
                                 __import__("os").path.abspath(__file__))))
    assert "RING_OK" in out.stdout, out.stderr[-2000:]


def test_reduce_scatter_then_gather_is_all_reduce():
    n = len(jax.devices())
    mesh = make_mesh((n,), ("x",))
    x = jnp.arange(n * 2 * 2, dtype=jnp.float32).reshape(n * 2, 2)

    def body(s):
        return reduce_scatter_then_gather(s, "x")

    got = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                            out_specs=P("x"), check_vma=False))(x)
    def ref(s):
        return jax.lax.psum(s, "x")
    want = jax.jit(jax.shard_map(ref, mesh=mesh, in_specs=P("x"),
                             out_specs=P("x"), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
