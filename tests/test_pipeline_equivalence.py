"""Parallelism-equivalence tier for pipeline strategies: `fsdp_pp<k>_mb<m>`
specs lowered through Strategy.to_plan must produce the same loss, grads,
and updated params as the pp=1 baseline (fp32, tiny transformer) —
including the grad-accumulation x pipeline-microbatch composition — and
a pp dry run must record the schedule's analytic terms in its artifact."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro import strategy as strategy_lib
from repro.configs import ShapeConfig, get_config, reduced
from repro.core import parallel as par
from repro.launch.specs import concrete_train_batch
from repro.models import transformer as tfm
from repro.models.layers import Runtime
from repro.optim import init_opt_state
from repro.train.trainer import (TrainConfig, make_train_step,
                                 place_train_state)

TOL = 1e-3


def _tiny_cfg():
    return reduced(get_config("qwen3-0.6b"), n_layers=4, d_model=128)


def _run_step(cfg, rt, tc, params, batch, plan=None):
    """One train step; sharded per plan when given, else single device."""
    step = make_train_step(cfg, rt, tc)
    opt = init_opt_state(params)
    if plan is None:
        return step(params, opt, batch)
    with par.use_mesh(plan.mesh):
        params_s, opt_s, batch_s, pshard, _ = place_train_state(
            cfg, plan, params, opt, batch)
        return jax.jit(step, out_shardings=(pshard, None, None))(
            params_s, opt_s, batch_s)


def _assert_equivalent(cfg, spec, grad_accum=1, global_batch=8, seq_len=32):
    topo = strategy_lib.host_topology()
    shape = ShapeConfig("eq", seq_len, global_batch, "train")
    strat = strategy_lib.parse(spec)
    plan = strat.to_plan(cfg, topo, shape)
    assert plan.pipe == "pipe" and plan.pipe_size == strat.pp

    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    batch = concrete_train_batch(cfg, global_batch, seq_len, key)
    tc = TrainConfig(grad_accum=grad_accum)

    rt1 = Runtime(attn_min_chunked_len=seq_len * 2)
    p1, _, m1 = _run_step(cfg, rt1, tc, params, batch)

    rt2 = par.make_runtime(cfg, plan, shape, param_dtype=jnp.float32,
                           compute_dtype=jnp.float32, remat=False,
                           attn_min_chunked_len=seq_len * 2)
    assert rt2.pipeline_microbatches == strat.microbatches
    p2, _, m2 = _run_step(cfg, rt2, tc, params, batch, plan)

    dl = abs(float(m1["loss"]) - float(m2["loss"]))
    g1, g2 = float(m1["grad_norm"]), float(m2["grad_norm"])
    rel_g = abs(g1 - g2) / max(g1, 1e-6)
    dp = max(float(jnp.max(jnp.abs(a - jax.device_get(b))))
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert dl < TOL, (spec, dl)
    assert rel_g < TOL, (spec, rel_g)
    assert dp < 1e-2, (spec, dp)


@pytest.mark.parametrize("spec", ["fsdp_pp2_mb4", "fsdp_pp4_mb8"])
def test_pp_matches_baseline(eight_devices, spec):
    """pp>1 loss/grads/updated params == pp=1 single-device baseline."""
    _assert_equivalent(_tiny_cfg(), spec)


@pytest.mark.parametrize("spec", ["fsdp_pp2_mb8_1f1b", "fsdp_pp2_mb4_1f1b",
                                  "fsdp_pp4_mb8_1f1b"])
def test_1f1b_matches_baseline(eight_devices, spec):
    """ISSUE 5 acceptance: 1F1B specs train end-to-end through the full
    Strategy lowering and match the sequential oracle (loss + grads) —
    the custom-vjp combined tick loop, not GPipe's transposed scan."""
    _assert_equivalent(_tiny_cfg(), spec)


@pytest.mark.parametrize("spec", ["fsdp_tp2_pp2_mb4", "fsdp_tp2_pp2_mb4_1f1b",
                                  "fsdp_cp2_pp2_mb4"])
def test_pp_composes_with_model_axis(eight_devices, spec):
    """ISSUE 5 acceptance: pp2 x tp2 (Megatron psums inside the stage;
    stage params stay model-sharded instead of replicated) and pp2 x cp2
    (sequence sharded inside the stage, gathered-KV attention) lower,
    train, and match the single-device baseline."""
    _assert_equivalent(_tiny_cfg(), spec)


def test_pp_composes_with_grad_accum(eight_devices):
    """GA slices the batch, the pipeline splits each slice into M
    microbatches; loss/grad scaling must match the GA-only baseline."""
    _assert_equivalent(_tiny_cfg(), "fsdp_pp2_mb2_ga2", grad_accum=2)


def test_pp_threads_moe_aux_loss(eight_devices):
    """pp > 1 now composes with MoE: the aux load-balance loss rides
    through the GPipe schedule alongside each microbatch (ISSUE 4
    satellite — the StrategyError that blocked MoE pipelines is gone).
    The per-microbatch aux averaging differs from the full-batch stats by
    O(1/sqrt(T_mb)) * aux_coef, hence the slightly wider tolerance."""
    import dataclasses as dc
    cfg = reduced(get_config("deepseek-moe-16b"), n_layers=4, d_model=128)
    cfg = dc.replace(cfg, moe=dc.replace(cfg.moe, moe_start_layer=0,
                                         capacity_factor=8.0))
    topo = strategy_lib.host_topology()
    shape = ShapeConfig("eq", 32, 8, "train")
    strat = strategy_lib.parse("fsdp_pp2_mb4")
    plan = strat.to_plan(cfg, topo, shape)     # no StrategyError for MoE

    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    batch = concrete_train_batch(cfg, 8, 32, key)
    tc = TrainConfig()

    rt1 = Runtime(attn_min_chunked_len=64, moe_impl="dropping", moe_groups=1)
    p1, _, m1 = _run_step(cfg, rt1, tc, params, batch)
    rt2 = par.make_runtime(cfg, plan, shape, param_dtype=jnp.float32,
                           compute_dtype=jnp.float32, remat=False,
                           attn_min_chunked_len=64)
    p2, _, m2 = _run_step(cfg, rt2, tc, params, batch, plan)

    assert float(m2["aux"]) > 0.0              # the aux loss is not dropped
    dl = abs(float(m1["loss"]) - float(m2["loss"]))
    assert dl < 2e-3, dl
    rel_g = abs(float(m1["grad_norm"]) - float(m2["grad_norm"])) \
        / max(float(m1["grad_norm"]), 1e-6)
    assert rel_g < 2e-3, rel_g
    dp = max(float(jnp.max(jnp.abs(a - jax.device_get(b))))
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert dp < 1e-2, dp


def test_pp_composes_with_ep(eight_devices):
    """ISSUE 5 acceptance: pp2 x ep2 — impossible before this refactor
    (StrategyError) — lowers and matches the non-pipelined dropping
    baseline: MoE layers inside the stage dispatch through the expert
    all-to-all on the 'expert' axis (no nested shard_map), both
    schedules."""
    import dataclasses as dc
    cfg = reduced(get_config("deepseek-moe-16b"), n_layers=4, d_model=128)
    cfg = dc.replace(cfg, moe=dc.replace(cfg.moe, moe_start_layer=0,
                                         capacity_factor=8.0))
    topo = strategy_lib.host_topology()
    shape = ShapeConfig("eq", 32, 8, "train")
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    batch = concrete_train_batch(cfg, 8, 32, key)
    tc = TrainConfig()

    # oracle: non-pipelined dropping with 4 groups == the 4 (data, expert)
    # token shards the pipeline stage dispatches from
    rt1 = Runtime(attn_min_chunked_len=64, moe_impl="dropping", moe_groups=4)
    p1, _, m1 = _run_step(cfg, rt1, tc, params, batch)

    for spec in ("fsdp_pp2_ep2_mb2", "fsdp_pp2_ep2_mb2_1f1b"):
        strat = strategy_lib.parse(spec)
        plan = strat.to_plan(cfg, topo, shape)   # no StrategyError anymore
        assert plan.pipe == "pipe" and plan.expert == "expert"
        rt2 = par.make_runtime(cfg, plan, shape, param_dtype=jnp.float32,
                               compute_dtype=jnp.float32, remat=False,
                               attn_min_chunked_len=64)
        p2, _, m2 = _run_step(cfg, rt2, tc, params, batch, plan)
        assert float(m2["aux"]) > 0.0            # aux loss not dropped
        dl = abs(float(m1["loss"]) - float(m2["loss"]))
        assert dl < 2e-3, (spec, dl)
        rel_g = abs(float(m1["grad_norm"]) - float(m2["grad_norm"])) \
            / max(float(m1["grad_norm"]), 1e-6)
        assert rel_g < 2e-3, (spec, rel_g)
        dp = max(float(jnp.max(jnp.abs(a - jax.device_get(b))))
                 for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert dp < 1e-2, (spec, dp)


def test_pp_tp_ep_triple_composition(eight_devices):
    """The full inner mesh at once: pipe2 x model2 x expert2 (all 8
    devices, data axis 1) under 1F1B — Megatron psums, expert all-to-all
    and the pipeline schedule composing in one stage body."""
    import dataclasses as dc
    cfg = reduced(get_config("deepseek-moe-16b"), n_layers=4, d_model=128)
    cfg = dc.replace(cfg, moe=dc.replace(cfg.moe, moe_start_layer=0,
                                         capacity_factor=8.0))
    topo = strategy_lib.host_topology()
    shape = ShapeConfig("eq", 32, 8, "train")
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    batch = concrete_train_batch(cfg, 8, 32, key)
    tc = TrainConfig()

    # oracle groups == the 2 expert-axis token shards the stage dispatches
    rt1 = Runtime(attn_min_chunked_len=64, moe_impl="dropping", moe_groups=2)
    p1, _, m1 = _run_step(cfg, rt1, tc, params, batch)

    strat = strategy_lib.parse("fsdp_tp2_pp2_ep2_mb2_1f1b")
    plan = strat.to_plan(cfg, topo, shape)
    assert dict(plan.mesh.shape) == {"pipe": 2, "data": 1, "expert": 2,
                                     "model": 2}
    rt2 = par.make_runtime(cfg, plan, shape, param_dtype=jnp.float32,
                           compute_dtype=jnp.float32, remat=False,
                           attn_min_chunked_len=64)
    p2, _, m2 = _run_step(cfg, rt2, tc, params, batch, plan)
    assert float(m2["aux"]) > 0.0
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-3
    rel_g = abs(float(m1["grad_norm"]) - float(m2["grad_norm"])) \
        / max(float(m1["grad_norm"]), 1e-6)
    assert rel_g < 2e-3, rel_g
    dp = max(float(jnp.max(jnp.abs(a - jax.device_get(b))))
             for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert dp < 1e-2, dp


def test_pp_ep_needs_expert_sharded_microbatch(eight_devices):
    """pp x ep with microbatch rows that cannot shard over the expert
    axis is rejected at to_plan (the in-stage all-to-all would overcount
    expert grads on replicated tokens)."""
    import dataclasses as dc
    from repro.strategy import StrategyError
    cfg = reduced(get_config("deepseek-moe-16b"), n_layers=4, d_model=128)
    cfg = dc.replace(cfg, moe=dc.replace(cfg.moe, moe_start_layer=0))
    topo = strategy_lib.host_topology()
    shape = ShapeConfig("eq", 32, 8, "train")
    with pytest.raises(StrategyError):
        # 8 / mb4 = 2 rows over data2 x expert2: expert axis unoccupied
        strategy_lib.parse("fsdp_pp2_ep2_mb4").to_plan(cfg, topo, shape)


def test_pp_matches_executed_fsdp_strategy(eight_devices):
    """pp>1 also agrees with the *executed* fsdp strategy (not just the
    single-device oracle): same lowering API, two points of the space."""
    cfg = _tiny_cfg()
    topo = strategy_lib.host_topology()
    shape = ShapeConfig("eq", 32, 8, "train")
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(cfg, key)
    batch = concrete_train_batch(cfg, 8, 32, key)
    tc = TrainConfig()

    metrics = {}
    for spec in ("fsdp", "fsdp_pp2_mb4"):
        plan = strategy_lib.parse(spec).to_plan(cfg, topo, shape)
        rt = par.make_runtime(cfg, plan, shape, param_dtype=jnp.float32,
                              compute_dtype=jnp.float32, remat=False,
                              attn_min_chunked_len=64)
        _, _, m = _run_step(cfg, rt, tc, params, batch, plan)
        metrics[spec] = m
    dl = abs(float(metrics["fsdp"]["loss"])
             - float(metrics["fsdp_pp2_mb4"]["loss"]))
    assert dl < TOL, dl


def test_train_cli_pp_on_kernels(eight_devices, tmp_path):
    """The acceptance command: --strategy fsdp_pp2_mb8 --kernels pallas
    completes training steps on 8 virtual CPU devices."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)         # train.py forces 8 fake devices
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--strategy", "fsdp_pp2_mb8", "--kernels", "pallas",
         "--reduced", "--steps", "2", "--seq_len", "64", "--log_every", "1"],
        capture_output=True, text=True, timeout=1200, env=env)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert "done: loss" in res.stdout, res.stdout[-3000:]


@pytest.mark.slow
def test_dryrun_artifact_records_the_pipeline_schedule(eight_devices,
                                                      tmp_path):
    """A pp>1 dry run writes the schedule's analytic terms into its
    artifact: the cost model's (P-1)/(M+P-1) bubble, the in-flight count
    and the table's sub-tick census."""
    from repro.launch import dryrun
    rec = dryrun.run_one("qwen3-0.6b", "train_4k", False, str(tmp_path),
                         strategy="fsdp_pp2_mb8", topology="host",
                         use_reduced=True)
    assert rec["status"] == "ok", rec
    _, label = dryrun.run_label("qwen3-0.6b", "train_4k", False,
                                "fsdp_pp2_mb8", "", "host")
    with open(os.path.join(str(tmp_path), label + ".json")) as f:
        artifact = json.load(f)
    pipe = artifact["pipeline"]
    assert pipe["pp"] == 2 and pipe["microbatches"] == 8
    assert pipe["bubble_predicted"] == pytest.approx(1 / 9)
    assert "inflight_microbatches" in pipe
    assert "op_tick_counts" in pipe
