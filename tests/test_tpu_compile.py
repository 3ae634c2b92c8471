"""Compile the Pallas kernels of the main path for a described TPU v5e.

Nothing runs: ``get_topology_desc`` describes a ``v5e:2x2`` slice that is
not attached, and each case lowers and compiles one kernel for its first
chip at the widths the model configs publish.  The chip's compiler refuses
block shapes that are not tiled to (8, 128), unsupported ops and kernels
that overflow VMEM -- faults the interpret-mode tests in
``test_kernels.py`` cannot see.  Every case asserts that each of its named
kernels survived as a ``tpu_custom_call`` in the compiled program.  One
more test compiles a small fsdp train step over the whole slice and reads
how the chip's compiler exchanges its weight gradients.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import strategy as strategy_lib
from repro.configs import ShapeConfig, get_config, reduced
from repro.core import compat
from repro.core import parallel as par
from repro.kernels import ops
from repro.models import transformer as tfm
from repro.optim import init_opt_state
from repro.perf.hlo import all_reduces, pallas_kernels
from repro.train.trainer import TrainConfig, jit_train_step, make_train_step

DTYPES = [jnp.bfloat16, jnp.float32]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_fwd(q, k, v):
    return ops.attention(q, k, v, interpret=False)


def _flash_fwd_bwd(q, k, v, cot):
    out, pull = jax.vjp(_flash_fwd, q, k, v)
    return out, pull(cot)


def _rmsnorm_fwd(x, s):
    return ops.rmsnorm(x, s, interpret=False)


def _rmsnorm_fwd_bwd(x, s, cot):
    out, pull = jax.vjp(_rmsnorm_fwd, x, s)
    return out, pull(cot)


def _decode(q, k_pool, v_pool, tbl, ctx):
    return ops.paged_decode_attention(q, k_pool, v_pool, tbl, ctx,
                                      interpret=False)


def _wkv6(r, k, v, w, u):
    return ops.wkv6(r, k, v, w, u, chunk=64, interpret=False)


# qwen3-0.6b: 16 query / 8 kv heads of 128, d_model 1024; rwkv6-1.6b:
# 32 heads of 64; qwen2-1.5b: d_model 1536.  Decode pools hold (blocks, kv
# heads, 16 tokens, 128).
B, S, H, KV, D, DM = 1, 2048, 16, 8, 128, 1024
Q, K, X = (B, S, H, D), (B, S, KV, D), (4, 512, DM)
X_WIDE = (1, 1024, 1536)
R = (1, 512, 32, 64)
FWD, DQ, DKV = ("flash_attention_fwd", "flash_attention_bwd_dq",
                "flash_attention_bwd_dkv")
F32 = jnp.float32
# name -> (fn, arg shapes for a dtype, the kernels it must compile)
CASES = {
    "flash_fwd": (_flash_fwd, lambda dt: [(Q, dt), (K, dt), (K, dt)],
                  {FWD}),
    "flash_fwd_bwd": (_flash_fwd_bwd,
                      lambda dt: [(Q, dt), (K, dt), (K, dt), (Q, dt)],
                      {FWD, DQ, DKV}),
    "rmsnorm_fwd": (_rmsnorm_fwd, lambda dt: [(X, dt), ((DM,), F32)],
                    {"rmsnorm_fwd"}),
    "rmsnorm_fwd_bwd": (_rmsnorm_fwd_bwd,
                        lambda dt: [(X, dt), ((DM,), F32), (X, dt)],
                        {"rmsnorm_fwd", "rmsnorm_bwd"}),
    "rmsnorm_fwd_bwd_wide": (_rmsnorm_fwd_bwd, lambda dt: [
        (X_WIDE, dt), ((X_WIDE[-1],), F32), (X_WIDE, dt)],
        {"rmsnorm_fwd", "rmsnorm_bwd"}),
    "flash_decode": (_decode, lambda dt: [
        ((4, 1, H, D), dt), ((64, KV, 16, D), dt), ((64, KV, 16, D), dt),
        ((4, 16), jnp.int32), ((4,), jnp.int32)], {"flash_decode"}),
    "wkv6_fwd": (_wkv6, lambda dt: [(R, dt)] * 4 + [((32, 64), dt)],
                 {"wkv6_fwd"}),
}


@pytest.mark.parametrize("dtype", DTYPES, ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, dtype, one_chip):
    fn, shapes, kernels = CASES[case]
    found = pallas_kernels(_compile_text(fn, one_chip, *shapes(dtype)))
    assert kernels <= set(found), (case, found)


def _fsdp4_step_text(cfg, topo):
    """A small ``fsdp_bf16`` train step of ``cfg`` over the four chips of
    the described slice -> its compiled text."""
    shape = ShapeConfig("t", 128, 4, "train")
    host = strategy_lib.host_topology(n_devices=4)
    strat, _ = strategy_lib.resolve("fsdp_bf16", cfg, host, shape)
    plan = strat.to_plan(cfg, host, shape, abstract=True)
    mesh = compat.make_mesh(plan.mesh.axis_sizes, plan.mesh.axis_names,
                            devices=topo.devices)
    plan = dataclasses.replace(plan, mesh=mesh)
    rt = par.make_runtime(cfg, plan, shape, remat=False)
    pshapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                             jax.random.PRNGKey(0))
    oshapes = jax.eval_shape(init_opt_state, pshapes)
    tok = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                               jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    pshard = par.param_shardings(cfg, plan, pshapes)
    oshard = {"m": pshard, "v": pshard, "step": par.fitted(plan, par.P(), ())}
    with par.use_mesh(mesh):
        step = jit_train_step(make_train_step(cfg, rt, TrainConfig()), pshard,
                              oshard, par.batch_specs(cfg, plan, batch))
        return step.lower(pshapes, oshapes, batch).compile().as_text()


def test_fsdp4_weight_gradients_reduce_scatter(topo):
    """Every weight matrix's gradient leaves a qwen2-shaped fsdp step over
    four chips as a reduce-scatter.  A full all-reduce may carry only
    vectors: biases and norm scales (at most one ``d_model`` vector a
    layer), the loss and the gradient norm's partial sums."""
    # at d_model 512 the compiler already runs a last-dim shard's gradient
    # as a full all-reduce; at 256 it still reduce-scatters it
    cfg = reduced(get_config("qwen2-1.5b"), d_model=512)
    found = all_reduces(_fsdp4_step_text(cfg, topo))
    scattered = [c for c, _ in found if c.startswith("all-reduce-scatter")]
    full = [(c, dims) for c, results in found
            if not c.startswith("all-reduce-scatter")
            for dims in results
            if math.prod(dims) > cfg.n_layers * cfg.d_model]
    assert scattered, found
    assert not full, full
