"""The device trace finds the trunk by its XLA module's name: the module
that ``ModelSpec.build``'s served function lowers to is one that
``bench/modules.json`` lists as the trunk."""
import re

import pytest

from bench import trace
from tests.bench.conftest import TINY_CELLS


@pytest.mark.parametrize("model", [c["config"] for c in TINY_CELLS])
def test_the_served_trunk_module_is_listed_as_the_trunk(model):
    import jax
    import jax.numpy as jnp

    from repro.core.models import make_model

    _cfg, params, serve_fn, _rules = make_model(model).build(canvas=128)
    x = jax.ShapeDtypeStruct((1, 128, 128, 3), jnp.float32)
    text = serve_fn.lower(params, x).as_text()
    name = re.match(r"module @(\S+)", text).group(1)
    assert name in trace.load_modules()["trunk"], name
