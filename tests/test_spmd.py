"""Multi-device SPMD equivalence (promoted from the ad-hoc
tests/spmd_check.py subprocess script): the sharded train step
(FSDP x TP / context-parallel plans on a (2, 4) mesh) produces the same
loss/gradients as the single-device step, and a sharded decode step
matches the unsharded one — in-process on the shared 8-virtual-device
configuration from conftest."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import strategy as strategy_lib
from repro.configs import ShapeConfig, get_config, reduced
from repro.core import parallel as par
from repro.launch.specs import concrete_train_batch
from repro.models import transformer as tfm
from repro.models.layers import Runtime
from repro.optim import init_opt_state
from repro.train.trainer import (TrainConfig, make_train_step,
                                 place_train_state)

TOL = 5e-3


def _plan(cfg, shape, attn_override=None):
    """(2, 4) data x model plan over the host devices, via the unified
    Strategy API (the deprecated choose_plan shim is no longer used)."""
    s = strategy_lib.Strategy(dp_mode="fsdp", tp=4, attn=attn_override)
    return s.to_plan(cfg, strategy_lib.host_topology(), shape)


def _check_train(arch: str, attn_override=None):
    cfg = reduced(get_config(arch), d_model=256)
    shape = ShapeConfig("t", 64, 4, "train")
    plan = _plan(cfg, shape, attn_override)
    mesh = plan.mesh
    rt_single = Runtime(rwkv_chunk=8, mamba_chunk=8, moe_impl="dropping",
                        moe_groups=1, attn_min_chunked_len=32,
                        attn_q_chunk=16, attn_kv_chunk=16)
    rt_shard = par.make_runtime(
        cfg, plan, shape, param_dtype=jnp.float32, compute_dtype=jnp.float32,
        remat=False, rwkv_chunk=8, mamba_chunk=8,
        attn_min_chunked_len=32, attn_q_chunk=64 if plan.attn == "context" else 16,
        attn_kv_chunk=16, moe_impl="dropping")

    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    batch = concrete_train_batch(cfg, shape.global_batch, shape.seq_len, key)
    tc = TrainConfig()

    # single device
    p1, o1, m1 = make_train_step(cfg, rt_single, tc)(
        params, init_opt_state(params), batch)

    # sharded
    with par.use_mesh(mesh):
        params_s, opt_s, batch_s, pshard, _ = place_train_state(
            cfg, plan, params, init_opt_state(params), batch)
        step = jax.jit(make_train_step(cfg, rt_shard, tc),
                       out_shardings=(pshard, None, None))
        p2, o2, m2 = step(params_s, opt_s, batch_s)

    dl = abs(float(m1["loss"]) - float(m2["loss"]))
    dg = abs(float(m1["grad_norm"]) - float(m2["grad_norm"]))
    rel_g = dg / max(float(m1["grad_norm"]), 1e-6)
    # updated params agree
    dp = max(float(jnp.max(jnp.abs(a - jax.device_get(b))))
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert dl < TOL, (arch, dl)
    assert rel_g < TOL, (arch, rel_g)
    assert dp < 5e-2, (arch, dp)


def _check_decode(arch: str):
    cfg = reduced(get_config(arch), d_model=256)
    shape = ShapeConfig("d", 64, 4, "decode")
    plan = _plan(cfg, shape)
    mesh = plan.mesh
    rt0 = Runtime(rwkv_chunk=8, mamba_chunk=8, moe_impl="dense")
    rt_s = par.make_runtime(cfg, plan, shape, param_dtype=jnp.float32,
                            compute_dtype=jnp.float32, remat=False,
                            rwkv_chunk=8, mamba_chunk=8, moe_impl="dense")

    key = jax.random.PRNGKey(1)
    params = tfm.init_params(cfg, key)
    B, S0 = shape.global_batch, 17
    tokens = jax.random.randint(key, (B, S0 + 1), 0, cfg.vocab_size)

    _, cache0 = tfm.prefill(cfg, params, {"tokens": tokens[:, :S0]}, rt0,
                            max_len=shape.seq_len)
    logits0, _ = tfm.decode_step(cfg, params, cache0, tokens[:, S0:],
                                 jnp.asarray(S0, jnp.int32), rt0)

    with par.use_mesh(mesh):
        pshard = par.param_shardings(cfg, plan, jax.eval_shape(lambda: params))
        params_s = jax.device_put(params, pshard)
        cshapes = jax.eval_shape(lambda: cache0)
        cshard = par.cache_shardings(cfg, plan, cshapes)
        cache_s = jax.device_put(cache0, cshard)
        logits_s, _ = jax.jit(
            lambda p, c, t, pos: tfm.decode_step(cfg, p, c, t, pos, rt_s),
            out_shardings=(None, cshard))(
                params_s, cache_s, tokens[:, S0:], jnp.asarray(S0, jnp.int32))

    err = float(jnp.max(jnp.abs(logits0 - jax.device_get(logits_s))))
    assert err < TOL, (arch, err)


@pytest.mark.slow
@pytest.mark.parametrize("arch,attn_override", [
    ("qwen3-0.6b", None),                    # head_tp
    ("qwen2-1.5b", "context"),               # CP
    ("rwkv6-1.6b", None),
    ("jamba-v0.1-52b", None),
    ("deepseek-moe-16b", None),
])
def test_sharded_train_equivalence(eight_devices, arch, attn_override):
    _check_train(arch, attn_override)


@pytest.mark.slow
@pytest.mark.parametrize("arch", [
    "qwen3-0.6b", "h2o-danube-1.8b", "jamba-v0.1-52b",
])
def test_sharded_decode_equivalence(eight_devices, arch):
    _check_decode(arch)


# the tensor-parallel layout: the model axis owns the leading dim of the
# output projections and of the vocabulary, the fsdp axis their last dim
TP_SPECS = {
    "['embed']['tok']": P("model", "data"),
    "['blocks'][0]['mixer']['wq']": P(None, "data", "model"),
    "['blocks'][0]['mixer']['wo']": P(None, "model", "data"),
    "['blocks'][0]['ffn']['w_up']": P(None, "data", "model"),
    "['blocks'][0]['ffn']['w_down']": P(None, "model", "data"),
}


def _fsdp4_specs(strategy):
    """{leaf path: (shape, spec)} of ``param_shardings`` for qwen2 cut
    small, planned by ``strategy`` over four host devices."""
    cfg = reduced(get_config("qwen2-1.5b"), d_model=256)
    shape = ShapeConfig("t", 64, 4, "train")
    topo = strategy_lib.host_topology(n_devices=4)
    strat, _ = strategy_lib.resolve(strategy, cfg, topo, shape)
    plan = strat.to_plan(cfg, topo, shape)
    pshapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                             jax.random.PRNGKey(0))
    pshard = par.param_shardings(cfg, plan, pshapes)
    return {jax.tree_util.keystr(path): (leaf.shape, sharding.spec)
            for (path, leaf), sharding in zip(
                jax.tree_util.tree_leaves_with_path(pshapes),
                jax.tree.leaves(pshard))}


@pytest.mark.parametrize("strategy", ["fsdp_bf16", "fsdp_tp2_bf16"])
def test_fsdp_shards_leading_dim_without_tp(eight_devices, strategy):
    """With no tensor parallelism the fsdp axis shards the leading non-stack
    dim of every weight matrix (``w_down``, ``wo`` and ``tok`` among them);
    a tensor-parallel plan keeps the model axis on those leading dims."""
    specs = _fsdp4_specs(strategy)
    matrices = {k: (shape, spec) for k, (shape, spec) in specs.items()
                if len(shape) - ("['blocks']" in k) == 2}
    assert {"['embed']['tok']", "['blocks'][0]['mixer']['wo']",
            "['blocks'][0]['ffn']['w_down']"} <= set(matrices)
    if strategy == "fsdp_bf16":
        for k, (shape, spec) in matrices.items():
            lead = len(shape) - 2
            assert spec[lead] == "data", (k, spec)
            assert "data" not in spec[lead + 1:], (k, spec)
    else:
        for k, want in TP_SPECS.items():
            assert specs[k][1] == want, (k, specs[k][1])
