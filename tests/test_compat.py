"""The mesh/collective names `repro.compat` re-exports from JAX.

The cluster layer (`repro.cluster`) and the sharded serve sessions call
``set_mesh``, ``axis_size`` and ``optimization_barrier`` through
`repro.compat`; these tests pin the behavior those call sites rely on.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat


def test_set_mesh_is_context_manager(mesh11):
    """``with jax.set_mesh(mesh)`` must work — the spelling every call site
    (cluster, tests, examples) uses."""
    with jax.set_mesh(mesh11):
        x = jnp.ones((4,))
    np.testing.assert_array_equal(np.asarray(x), 1.0)
    # compat.set_mesh is the same entry point
    with compat.set_mesh(mesh11):
        pass


def test_axis_size_inside_shard_map(mesh11):
    """``compat.axis_size`` must return a *concrete* int under tracing (the
    cluster layer uses it in Python control flow to flatten shard indices)."""
    sizes = {}

    def body(x):
        sizes["data"] = compat.axis_size("data")
        sizes["model"] = compat.axis_size("model")
        assert isinstance(sizes["data"], (int, np.integer)) or sizes["data"].shape == ()
        idx = jax.lax.axis_index("data") * compat.axis_size("model") + jax.lax.axis_index("model")
        return x + idx

    fn = jax.shard_map(body, mesh=mesh11, in_specs=P(), out_specs=P(), check_vma=False)
    out = fn(jnp.zeros((2,)))
    assert int(sizes["data"]) == 1 and int(sizes["model"]) == 1
    np.testing.assert_array_equal(np.asarray(out), 0.0)  # shard 0 of a 1x1 mesh


def test_axis_size_matches_mesh_shape():
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    def body(x):
        return x * compat.axis_size("data")

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    np.testing.assert_array_equal(np.asarray(fn(jnp.full((2,), 3.0))), 3.0)


def test_optimization_barrier_differentiable():
    """The barrier must be identity-valued with identity JVP."""
    y, t = jax.jvp(compat.optimization_barrier, (2.0,), (5.0,))
    assert float(y) == 2.0 and float(t) == 5.0
    g = jax.grad(lambda x: compat.optimization_barrier(x * x))(3.0)
    assert float(g) == 6.0
