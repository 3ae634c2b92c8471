"""Pipeline schedule unit tests (promoted from the ad-hoc
tests/pipeline_check.py subprocess script): GPipe-scheduled layers over a
'pipe' mesh axis == sequential application, forward AND gradient, on the
shared 8-virtual-device fixture — including the composed (pipe, data) mesh
the Strategy lowering builds."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.core.compat import make_mesh, use_mesh
from repro.core.pipeline import (batch_axes_spec, bubble_fraction,
                                 make_pipelined_block_fn, pipeline_apply)
from repro.models.layers import Runtime
from repro.models.transformer import (_apply_layer, _init_layer, _sig,
                                      _tree_stack)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen3-0.6b"), n_layers=4, d_model=128)
    rt = Runtime()
    key = jax.random.PRNGKey(0)
    layers = [_init_layer(cfg, i, k) for i, k in
              enumerate(jax.random.split(key, 4))]
    # stacked layer params, leading dim = total layers (the pipe axis
    # shards it into contiguous stages)
    stacked = {"layers": _tree_stack(layers)}
    return cfg, rt, layers, stacked


def _sequential(cfg, rt, layers, x):
    M, mb, S, d = x.shape
    h = x.reshape(M * mb, S, d)
    for lp in layers:
        h, _, _ = _apply_layer(cfg, _sig(cfg, 0), lp, h, None, rt)
    return h.reshape(M, mb, S, d)


@pytest.mark.parametrize("mesh_axes", [("pipe",), ("pipe", "data")])
def test_pipeline_matches_sequential_fwd_and_grad(setup, eight_devices,
                                                  mesh_axes):
    cfg, rt, layers, stacked = setup
    if mesh_axes == ("pipe",):
        mesh = make_mesh((4,), mesh_axes, devices=eight_devices[:4])
        batch_axes = ()
    else:
        mesh = make_mesh((4, 2), mesh_axes, devices=eight_devices)
        batch_axes = ("data",)
    M, mb, S, d = 8, 2, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(0), (M, mb, S, d)) * 0.5
    stage_fn = make_pipelined_block_fn(cfg, rt)

    def pipelined(params, x):
        out, _aux = pipeline_apply(stage_fn, params, x, mesh, "pipe",
                                   batch_axes=batch_axes)
        return out

    with use_mesh(mesh):
        out_p = jax.jit(pipelined)(stacked, x)
    out_s = _sequential(cfg, rt, layers, x)
    assert float(jnp.max(jnp.abs(out_p - out_s))) < 1e-4

    # gradient path through shard_map + ppermute (reverse schedule)
    def loss_p(params):
        return jnp.sum(pipelined(params, x) ** 2)

    def loss_s(layers):
        return jnp.sum(_sequential(cfg, rt, layers, x) ** 2)

    with use_mesh(mesh):
        g_p = jax.jit(jax.grad(loss_p))(stacked)
    g_s = {"layers": _tree_stack(jax.grad(loss_s)(layers))}
    errs = [float(jnp.max(jnp.abs(a - b))) for a, b in
            zip(jax.tree.leaves(g_p), jax.tree.leaves(g_s))]
    assert max(errs) < 5e-3, max(errs)


def test_pipeline_multi_layer_stages(setup, eight_devices):
    """4 layers over 2 stages: each stage scans its 2-layer local slice."""
    cfg, rt, layers, stacked = setup
    mesh = make_mesh((2,), ("pipe",), devices=eight_devices[:2])
    M, mb, S, d = 4, 2, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, S, d)) * 0.5
    stage_fn = make_pipelined_block_fn(cfg, rt)
    with use_mesh(mesh):
        out_p = jax.jit(lambda p, x: pipeline_apply(
            stage_fn, p, x, mesh, "pipe")[0])(stacked, x)
    out_s = _sequential(cfg, rt, layers, x)
    assert float(jnp.max(jnp.abs(out_p - out_s))) < 1e-4


def test_bubble_fraction_formula():
    assert abs(bubble_fraction(4, 8) - 3 / 11) < 1e-9
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(2, 2) - 1 / 3) < 1e-9


def test_batch_axes_spec_fit_or_drop(eight_devices):
    mesh = make_mesh((2, 4), ("pipe", "data"), devices=eight_devices)
    assert batch_axes_spec(mesh, ("data",), 8) == ("data",)
    assert batch_axes_spec(mesh, ("data",), 3) == ()   # not divisible
    assert batch_axes_spec(mesh, ("data",), 1) == ()   # cannot occupy
