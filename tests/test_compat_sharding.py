"""The sharding compat layer (repro.compat.shardingx): thin calls to the
installed JAX's mesh and ambient-mesh API."""
import os

import jax
import jax.numpy as jnp

from repro.compat import shardingx
from repro.launch.mesh import (make_serve_mesh, make_test_mesh,
                               make_unit_mesh, mesh_chips)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class TestFeatureDetection:
    def test_flags_are_consistent(self):
        # the installed JAX offers every API the layer calls
        import inspect
        assert hasattr(jax.sharding, "AxisType")
        assert "axis_types" in inspect.signature(jax.make_mesh).parameters
        assert hasattr(jax.sharding, "set_mesh")
        assert hasattr(jax.sharding, "get_abstract_mesh")

    def test_auto_axis_types_matches_gate(self):
        assert shardingx.auto_axis_types(3) == \
            (jax.sharding.AxisType.Auto,) * 3
        mesh = make_unit_mesh()
        assert tuple(mesh.axis_types) == shardingx.auto_axis_types(2)


class TestMakeMesh:
    def test_unit_mesh(self):
        mesh = make_unit_mesh()
        assert tuple(mesh.axis_names) == ("data", "model")
        assert mesh.devices.shape == (1, 1)
        assert mesh_chips(mesh) == 1

    def test_serve_mesh_covers_local_devices(self):
        mesh = make_serve_mesh()
        assert tuple(mesh.axis_names) == ("data", "model")
        assert mesh_chips(mesh) == len(jax.devices())

    def test_serve_mesh_device_subset(self):
        mesh = make_serve_mesh(1)           # explicit count < world size OK
        assert mesh_chips(mesh) == 1

    def test_mesh_from_devices_roundtrip(self):
        mesh = make_unit_mesh()
        rebuilt = shardingx.mesh_from_devices(mesh.devices, mesh.axis_names)
        assert tuple(rebuilt.axis_names) == tuple(mesh.axis_names)
        assert rebuilt.devices.shape == mesh.devices.shape


class TestUseMesh:
    def test_jit_lowers_inside_ctx(self):
        mesh = make_unit_mesh()
        with shardingx.use_mesh(mesh):
            out = jax.jit(lambda x: x * 2)(jnp.arange(4.0))
        assert float(out.sum()) == 12.0

    def test_get_abstract_mesh_never_raises(self):
        assert shardingx.get_abstract_mesh() is None  # outside any ctx

    def test_ambient_mesh_visible_inside_ctx(self):
        """The ambient mesh is visible inside use_mesh — otherwise logical
        sharding constraints would silently no-op."""
        mesh = make_unit_mesh()
        with shardingx.use_mesh(mesh):
            ambient = shardingx.get_abstract_mesh()
            assert ambient is not None
            assert shardingx.mesh_axis_sizes(ambient) == \
                {"data": 1, "model": 1}
        assert shardingx.get_abstract_mesh() is None

    def test_logical_constraint_applies_inside_ctx(self):
        from repro.sharding import DEFAULT_RULES, with_logical_constraint
        mesh = make_unit_mesh()
        with shardingx.use_mesh(mesh):
            out = jax.jit(lambda x: with_logical_constraint(
                x, ("batch", "embed"), DEFAULT_RULES))(jnp.ones((4, 8)))
        assert out.shape == (4, 8)


class TestCostAnalysisDict:
    class _Compiled:
        def __init__(self, ca):
            self._ca = ca

        def cost_analysis(self):
            return self._ca

    def test_new_jax_dict_form(self):
        assert shardingx.cost_analysis_dict(
            self._Compiled({"flops": 5.0})) == {"flops": 5.0}
        assert shardingx.cost_analysis_dict(self._Compiled(None)) == {}

    def test_real_compiled_artifact(self):
        ca = shardingx.cost_analysis_dict(
            jax.jit(lambda x: x @ x).lower(
                jnp.ones((8, 8), jnp.float32)).compile())
        assert isinstance(ca, dict)
        assert float(ca.get("flops", 0.0)) > 0


def test_no_axis_type_references_outside_compat():
    """The whole point of the layer: ``jax.sharding.AxisType`` must only
    ever be touched inside repro/compat/ — everything else routes through
    the factory, so an API move is repaired in one file."""
    offenders = []
    for root, _, files in os.walk(SRC):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            if os.sep + "compat" + os.sep in path:
                continue
            with open(path) as f:
                if "AxisType" in f.read():
                    offenders.append(os.path.relpath(path, SRC))
    assert offenders == [], f"AxisType referenced outside compat: {offenders}"
