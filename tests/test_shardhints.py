"""shardhints: logical-axis constraints resolve/drop correctly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from _compat import given, settings, st

from repro.models import shardhints as SH


def test_noop_without_mesh():
    SH.set_mesh(None)
    x = jnp.ones((4, 8))
    y = SH.constrain(x, SH.BATCH, SH.MODEL)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_resolve_batch_axes():
    m1 = AbstractMesh((16, 16), ("data", "model"))
    m2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert SH._resolve(m1, SH.BATCH) == ("data",)
    assert SH._resolve(m2, SH.BATCH) == ("pod", "data")
    assert SH._resolve(m1, SH.MODEL) == "model"
    assert SH._resolve(m1, None) is None
    assert SH._resolve(m1, "nonexistent") is None


@settings(max_examples=20, deadline=None)
@given(d0=st.integers(1, 64), d1=st.integers(1, 64))
def test_divisibility_fallback(d0, d1):
    """Axes that don't divide a dim must be dropped, never error."""
    mesh = AbstractMesh((16, 16), ("data", "model"))
    with SH.use_mesh(mesh):
        # tracing-time check via eval_shape (no devices needed)
        def f(x):
            return SH.constrain(x, SH.BATCH, SH.MODEL)
        out = jax.eval_shape(f, jax.ShapeDtypeStruct((d0, d1), jnp.float32))
        assert out.shape == (d0, d1)


def test_use_mesh_restores():
    mesh = AbstractMesh((16, 16), ("data", "model"))
    SH.set_mesh(None)
    with SH.use_mesh(mesh):
        assert SH.get_mesh() is mesh
    assert SH.get_mesh() is None


def test_no_double_axis_use():
    """The same mesh axis may not shard two dims of one tensor."""
    mesh = AbstractMesh((16, 16), ("data", "model"))
    with SH.use_mesh(mesh):
        def f(x):
            return SH.constrain(x, SH.MODEL, SH.MODEL)
        # second MODEL must be dropped silently -> shape preserved, no error
        out = jax.eval_shape(f, jax.ShapeDtypeStruct((32, 32), jnp.float32))
        assert out.shape == (32, 32)
