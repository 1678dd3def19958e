"""Logical-axis sharding rules (divisibility dropping, profiles) and the
ambient mesh (`enter_mesh` / `with_logical_constraint` over `jax.set_mesh`)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import (
    DEFAULT_RULES,
    FSDP_TP_RULES,
    enter_mesh,
    logical_to_spec,
    rules_for,
    tree_shardings,
    with_logical_constraint,
)

# A host-only mesh over the single CPU device would have size-1 axes, which
# can't exercise divisibility. Use an abstract mesh instead.


def abstract_mesh(sizes, names):
    return jax.sharding.AbstractMesh(
        sizes, names, axis_types=(jax.sharding.AxisType.Auto,) * len(names)
    )


def make_mesh():
    return abstract_mesh((2, 4), ("data", "model"))


def test_basic_mapping():
    mesh = make_mesh()
    spec = logical_to_spec(("vocab", "embed"), DEFAULT_RULES, mesh)
    assert spec == P("model")


def test_batch_uses_pod_and_data():
    mesh = abstract_mesh((2, 2, 4), ("pod", "data", "model"))
    spec = logical_to_spec(("batch", None, "embed"), DEFAULT_RULES, mesh)
    assert spec == P(("pod", "data"))


def test_non_divisible_axis_dropped():
    mesh = make_mesh()
    # 8 kv heads on a 4-way model axis: fine; 6 heads: dropped
    assert logical_to_spec(("kv_heads",), DEFAULT_RULES, mesh, shape=(8,)) == P("model")
    assert logical_to_spec(("kv_heads",), DEFAULT_RULES, mesh, shape=(6,)) == P()


def test_axis_never_reused_within_spec():
    mesh = make_mesh()
    # both vocab and ffn map to "model": second use must drop
    spec = logical_to_spec(("vocab", "ffn"), DEFAULT_RULES, mesh)
    assert spec == P("model")


def test_fsdp_profile_shards_embed_over_data():
    mesh = make_mesh()
    spec = logical_to_spec(("embed", "ffn"), FSDP_TP_RULES, mesh, shape=(8, 8))
    assert spec == P("data", "model")
    # but activations with a batch dim keep data for the batch
    spec = logical_to_spec(("batch", None, "embed"), FSDP_TP_RULES, mesh, shape=(8, 4, 8))
    assert spec == P("data")


def test_tree_shardings_with_shapes():
    mesh = make_mesh()
    axes = {"w": ("embed", "ffn"), "b": ("ffn",)}
    shapes = {
        "w": jax.ShapeDtypeStruct((16, 8), jax.numpy.float32),
        "b": jax.ShapeDtypeStruct((6,), jax.numpy.float32),  # 6 % 4 != 0
    }
    out = tree_shardings(axes, mesh, DEFAULT_RULES, shapes)
    assert out["w"].spec == P(None, "model")
    assert out["b"].spec == P()


def test_unknown_profile_raises():
    with pytest.raises(KeyError):
        rules_for("nope")


def test_actors_axis_rule_maps_to_data():
    mesh = abstract_mesh((2, 4), ("data", "model"))
    assert logical_to_spec(("actors",), DEFAULT_RULES, mesh) == P("data")


# ----------------------------------------------------------- ambient mesh


def device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def test_enter_mesh_installs_ambient_mesh():
    assert jax.sharding.get_abstract_mesh().empty
    with enter_mesh(device_mesh()):
        ambient = jax.sharding.get_abstract_mesh()
        assert not ambient.empty
        assert tuple(ambient.axis_names) == ("data",)
    assert jax.sharding.get_abstract_mesh().empty


def test_with_logical_constraint_is_noop_outside_mesh():
    x = jnp.arange(8.0)
    y = with_logical_constraint(x, ("batch",))
    assert y is x  # literally untouched, not just equal


def test_with_logical_constraint_applies_inside_mesh():
    x = jnp.arange(8.0).reshape(4, 2)

    @jax.jit
    def f(x):
        return with_logical_constraint(x, ("batch", None)) * 2

    with enter_mesh(device_mesh()):
        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x) * 2)
