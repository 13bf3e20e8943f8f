"""The JAX surfaces the repo builds on, exercised on the installed JAX:
Auto-typed meshes, ``jax.shard_map``, and the kernels' interpret switch."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.kernels import interpret_default
from repro.launch.mesh import make_host_mesh


def test_make_mesh_host_devices():
    n = len(jax.devices())
    mesh = make_host_mesh((n,), ("data",))
    assert isinstance(mesh, jax.sharding.Mesh)
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == n
    assert mesh.axis_types == (AxisType.Auto,)
    # multi-axis on a single device
    mesh2 = make_host_mesh((1, 1), ("data", "model"))
    assert mesh2.axis_names == ("data", "model")


def test_make_mesh_usable_for_sharding():
    mesh = make_host_mesh()
    sh = jax.sharding.NamedSharding(mesh, P())
    x = jax.device_put(jnp.arange(8.0), sh)
    np.testing.assert_array_equal(np.asarray(x), np.arange(8.0))


def test_shard_map_runs_and_matches_reference():
    n = len(jax.devices())
    mesh = make_host_mesh()
    x = jnp.arange(4 * n, dtype=jnp.float32).reshape(n, 4)

    def local(v):
        s = jax.lax.psum(jnp.sum(v), "data")
        return v * 2.0 + s

    fn = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))
    out = fn(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 2.0 + float(x.sum()))


def test_pallas_interpret_default_matches_backend():
    assert interpret_default() == (jax.default_backend() != "tpu")
