"""Sharding-rule tests on an abstract production-shaped mesh (no devices)."""

import jax
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

import repro.configs as cfgs
from repro.distributed import sharding as shr
from repro.models import Model


def _mesh(multi=False):
    if multi:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def _pshapes(arch):
    cfg = cfgs.get_config(arch)
    return cfg, jax.eval_shape(lambda: Model(cfg).init(jax.random.PRNGKey(0)))


def _check_divisibility(shapes, specs, mesh):
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    for sh, spec in zip(flat_shapes, flat_specs):
        for dim, axis in zip(sh.shape, tuple(spec) + (None,) * 8):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, f"{sh.shape} not divisible by {axis}={size}"


@pytest.mark.parametrize("arch", cfgs.ARCH_IDS)
@pytest.mark.parametrize("strategy", ["dp_tp", "fsdp_tp"])
def test_param_specs_divisible(arch, strategy):
    cfg, shapes = _pshapes(arch)
    mesh = _mesh()
    specs = shr.param_pspecs(shapes, cfg, mesh, strategy)
    _check_divisibility(shapes, specs, mesh)


def test_model_axis_actually_used():
    """TP must shard the big matmuls for every arch (not silently replicate)."""
    for arch in cfgs.ARCH_IDS:
        cfg, shapes = _pshapes(arch)
        mesh = _mesh()
        specs = shr.param_pspecs(shapes, cfg, mesh, "dp_tp")
        flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        used = any("model" in str(s) for s in flat)
        assert used, f"{arch}: no parameter sharded on the model axis"


def test_fsdp_shards_more_than_dp():
    cfg, shapes = _pshapes("mistral-large-123b")
    mesh = _mesh()
    dp = shr.param_pspecs(shapes, cfg, mesh, "dp_tp")
    fs = shr.param_pspecs(shapes, cfg, mesh, "fsdp_tp")

    def sharded_fraction(specs):
        flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        return sum("data" in str(s) for s in flat) / len(flat)

    assert sharded_fraction(fs) > sharded_fraction(dp)


def test_input_specs_batch_sharded():
    cfg = cfgs.get_config("qwen3-0.6b")
    mesh = _mesh(multi=True)
    ins = cfgs.input_specs(cfg, cfgs.SHAPES["train_4k"])
    specs = shr.input_pspecs(ins, mesh)
    tok = specs["tokens"]
    assert tok[0] == ("pod", "data")
    _check_divisibility(ins, specs, mesh)


def test_decode_cache_specs_divisible():
    for arch in ("mistral-large-123b", "jamba-v0.1-52b", "mamba2-2.7b"):
        cfg = cfgs.get_config(arch)
        mesh = _mesh()
        ins = cfgs.input_specs(cfg, cfgs.SHAPES["decode_32k"])
        specs = shr.input_pspecs(ins, mesh)
        _check_divisibility(ins, specs, mesh)


def test_batch_axes():
    assert shr.batch_axes(_mesh()) == ("data",)
    assert shr.batch_axes(_mesh(multi=True)) == ("pod", "data")
