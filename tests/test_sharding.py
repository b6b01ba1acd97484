"""Sharding-rule validation on an AbstractMesh of the production shape
(no devices needed): every spec axis must divide its dim."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import registry
from repro.configs.shapes import SHAPES
from repro.launch import sharding
from repro.models import api
from repro.optim import adamw as optim_mod

SINGLE = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(shapes_tree, specs_tree, mesh, where=""):
    flat_s = jax.tree.leaves(shapes_tree)
    flat_p = jax.tree.leaves(
        specs_tree, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p), where
    for sds, spec in zip(flat_s, flat_p):
        ents = tuple(spec)
        assert len(ents) <= len(sds.shape), (where, sds.shape, spec)
        for dim, entry in zip(sds.shape, ents):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (where, sds.shape, spec)


@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("arch", registry.ASSIGNED_ARCHS)
def test_param_specs_divide(arch, mesh):
    cfg = registry.get_config(arch)
    shapes = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    specs = sharding.param_pspecs(cfg, mesh)
    _check_divisible(shapes, specs, mesh, where=arch)


@pytest.mark.parametrize("arch", registry.ASSIGNED_ARCHS)
def test_state_specs_divide(arch):
    cfg = registry.get_config(arch)
    opt = optim_mod.for_config(cfg)
    from repro.core import fl_step
    state_shapes = jax.eval_shape(
        lambda: fl_step.init_state(jax.random.PRNGKey(0), cfg, opt))
    specs = sharding.state_pspecs(cfg, SINGLE, opt)
    _check_divisible(state_shapes, specs, SINGLE, where=arch)


@pytest.mark.parametrize("arch", registry.ASSIGNED_ARCHS)
@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_input_specs_divide(arch, shape_name):
    if shape_name == "long_500k" and arch in registry.LONG_CTX_SKIP:
        pytest.skip("skipped by design")
    cfg = registry.config_for_shape(arch, shape_name)
    shape = SHAPES[shape_name]
    if shape.kind == "train":
        specs = api.input_specs(cfg, shape, num_clients=16)
        pspecs = sharding.train_batch_pspecs(cfg, SINGLE, specs["batch"])
        _check_divisible(specs["batch"], pspecs, SINGLE,
                         where=f"{arch}/{shape_name}")
    elif shape.kind == "prefill":
        specs = api.input_specs(cfg, shape)
        pspecs = sharding.infer_batch_pspecs(SINGLE, specs["batch"])
        _check_divisible(specs["batch"], pspecs, SINGLE,
                         where=f"{arch}/{shape_name}")
    else:
        specs = api.input_specs(cfg, shape)
        cspecs = sharding.cache_pspecs(cfg, SINGLE, specs["cache"])
        _check_divisible(specs["cache"], cspecs, SINGLE,
                         where=f"{arch}/{shape_name}")


def test_expert_parallel_only_for_arctic():
    for arch in registry.ASSIGNED_ARCHS:
        cfg = registry.get_config(arch)
        if arch == "arctic-480b":
            assert cfg.expert_parallel and cfg.client_axes == ("pod",)
        else:
            assert not cfg.expert_parallel


def test_arctic_expert_sharding():
    cfg = registry.get_config("arctic-480b")
    specs = sharding.param_pspecs(cfg, SINGLE)
    wg = specs["layers"]["moe"]["wg"]       # (L, E, d, ff)
    assert tuple(wg) == (None, "data", None, "model")
    wd = specs["layers"]["moe"]["wd"]       # (L, E, ff, d)
    assert tuple(wd) == (None, "data", "model", None)


def test_sharded_step_runs_on_debug_mesh():
    """The sharded lowering path executes on a 1-device mesh."""
    from repro.core import fl_step
    from repro.launch import mesh as mesh_mod
    import numpy as np
    cfg = registry.get_config("qwen2-1.5b", smoke=True)
    mesh = mesh_mod.make_debug_mesh()
    opt = optim_mod.for_config(cfg)
    state = fl_step.init_state(jax.random.PRNGKey(0), cfg, opt)
    sspec = sharding.state_pspecs(cfg, mesh, opt)
    batch = {
        "tokens": jnp.zeros((2, 2, 16), jnp.int32),
        "labels": jnp.zeros((2, 2, 16), jnp.int32),
    }
    bspec = sharding.train_batch_pspecs(cfg, mesh, jax.eval_shape(
        lambda: batch))
    step = jax.jit(fl_step.make_raw_step(cfg, opt, theta=0.65),
                   in_shardings=(sharding.to_named(mesh, sspec),
                                 sharding.to_named(mesh, bspec)),
                   out_shardings=(sharding.to_named(mesh, sspec), None))
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
