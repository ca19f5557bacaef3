"""Sharding-rule properties and host-mesh execution of the pjit step
functions (the same code paths the 512-device dry-run lowers)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding as Sh
from repro.configs.base import INPUT_SHAPES, ShapeConfig, get_config, \
    list_configs
from repro.launch import steps as ST
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as T
from repro.optim import adamw_init

ARCHS = [a for a in list_configs() if a != "densenet-fl"]

def _fake_mesh():
    """Abstract 16x16 mesh for spec computation only (no devices
    needed)."""
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_divisible(arch):
    """Every sharded dim must divide by its mesh axis size."""
    cfg = get_config(arch)
    mesh = _fake_mesh()
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    specs = Sh.param_specs(shapes, cfg, mesh)

    def check(leaf, spec):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            size = mesh.shape[ax] if isinstance(ax, str) else \
                int(np.prod([mesh.shape[a] for a in ax]))
            assert dim % size == 0, (arch, leaf.shape, spec)

    jax.tree.map(check, shapes, specs,
                 is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


@pytest.mark.parametrize("arch", ["qwen3-8b", "mixtral-8x7b"])
def test_opt_specs_add_data_axis(arch):
    cfg = get_config(arch)
    mesh = _fake_mesh()
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    opt_shapes = jax.eval_shape(adamw_init, shapes)
    pspecs = Sh.param_specs(shapes, cfg, mesh)
    ospecs = Sh.opt_state_specs(opt_shapes, pspecs, cfg, mesh)
    n_data = sum(1 for s in jax.tree.leaves(
        ospecs["m"], is_leaf=lambda x: isinstance(x, P))
        if "data" in jax.tree_util.tree_leaves(tuple(s)))
    assert n_data > 0, "ZeRO-1 data-axis sharding never applied"


def test_moe_expert_sharding_rules():
    mesh = _fake_mesh()
    qcfg = get_config("qwen3-moe-30b-a3b")     # 128 experts: expert-parallel
    mcfg = get_config("mixtral-8x7b")          # 8 experts: shard d_ff
    qshapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                   qcfg))
    qspecs = Sh.param_specs(qshapes, qcfg, mesh)
    q_w = qspecs["stages"][0]["pos0"]["ffn"]["moe"]["w_gate"]
    assert tuple(q_w) [1] == "model"          # (layer, E, D, F): E sharded
    mshapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                   mcfg))
    mspecs = Sh.param_specs(mshapes, mcfg, mesh)
    m_w = mspecs["stages"][0]["pos0"]["ffn"]["moe"]["w_gate"]
    assert tuple(m_w)[-1] == "model"          # d_ff sharded instead


def test_production_mesh_shapes():
    # uses the 1-device CPU? make_production_mesh needs 256 devices — only
    # verify the *spec* of the function via AbstractMesh equivalence here.
    import inspect
    src = inspect.getsource(make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src.replace("'", '"')


@pytest.mark.parametrize("arch", ["qwen3-8b", "mamba2-370m",
                                  "mixtral-8x7b", "whisper-base"])
def test_train_step_runs_on_host_mesh(arch, key):
    """The exact train_step the dry-run lowers, executed for real on a tiny
    config and 1x1 mesh; loss must be finite and params must change."""
    cfg = get_config(arch).reduced()
    mesh = make_host_mesh()
    shape = ShapeConfig("tiny", seq_len=32, global_batch=2, kind="train")
    with jax.set_mesh(mesh) if hasattr(jax, "set_mesh") else mesh:
        step = ST.make_train_step(cfg, mesh, num_micro=2, q_chunk=16,
                                  lr=1e-3)
        params = T.init_params(key, cfg)
        opt = adamw_init(params)
        from repro.launch.input_specs import train_batch_specs
        specs = train_batch_specs(cfg, shape)
        batch = {k: jnp.zeros(v.shape, v.dtype) if v.dtype == jnp.int32
                 else jax.random.normal(key, v.shape, v.dtype)
                 for k, v in specs.items()}
        params2, opt2, metrics = jax.jit(step)(params, opt, batch)
    assert jnp.isfinite(metrics["loss"])
    diff = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(params),
                               jax.tree.leaves(params2)))
    assert diff > 0.0


@pytest.mark.parametrize("arch", ["qwen3-8b", "gemma3-12b"])
def test_serve_step_runs_on_host_mesh(arch, key):
    cfg = get_config(arch).reduced()
    mesh = make_host_mesh()
    with mesh:
        serve = ST.make_serve_step(cfg)
        params = T.init_params(key, cfg)
        state = T.init_decode_state(params, cfg, 2, 16, jnp.float32)
        tok = jnp.zeros((2, 1), jnp.int32)
        nxt, state = jax.jit(serve)(params, state, tok)
    assert nxt.shape == (2, 1)
    assert int(state["index"]) == 1
