"""Unified strategy API tests (ISSUE 1): spec round-trips, cost-model /
SPMD-lowering group-size agreement, and planner search contracts.

Group-size agreement uses AbstractMesh lowering (no devices needed), so
the 512-chip pod topology is exercised on any host; search-lowers tests
run on the real host mesh (however many devices pytest sees).

Property tests (hypothesis, skipped when it is not installed): every
spec string round-trips parse -> format -> parse, and for every valid
strategy the collective group sizes ``to_cost_strategy`` reports equal
the mesh axis sizes ``to_plan`` builds — including the 'pipe' axis.
"""
import dataclasses

import jax
import pytest
from hypothesis import assume, given, settings
from hypothesis import strategies as st

from repro import strategy as strategy_lib
from repro.configs import SHAPES, get_config, reduced
from repro.configs.base import ShapeConfig
from repro.configs.llama2 import LLAMA2_7B
from repro.core import costmodel as cm
from repro.core import parallel as par
from repro.strategy import (Strategy, StrategyError, Topology, parse,
                            pareto_front, search)

TRAIN = ShapeConfig("t", 4096, 256, "train")
POD2 = strategy_lib.pod_topology(pods=2)
POD1 = strategy_lib.pod_topology(pods=1)


# ---------------------------------------------------------------------------
# spec strings
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s", [
    Strategy(),
    Strategy(dp_mode="fsdp", tp=4),
    Strategy(dp_mode="hsdp", cp=8),
    Strategy(dp_mode="ddp"),
    Strategy(dp_mode="fsdp", tp=2, zero_stage=2, grad_accum=4),
    Strategy(dp_mode="hsdp", tp=4, microbatches=8, seq_parallel=False),
    Strategy(dp_mode="fsdp", pp=4, microbatches=16),
    Strategy(dp_mode="fsdp", tp=2, attn="context"),
    Strategy(dp_mode="hsdp", tp=8, attn="head_tp", zero_stage=3),
    Strategy(dp_mode="fsdp", ep=8),
    Strategy(dp_mode="hsdp", tp=2, ep=4),
    Strategy(dp_mode="fsdp", pp=4, microbatches=8, sched="1f1b"),
    Strategy(dp_mode="fsdp", tp=2, pp=2, ep=2, microbatches=4,
             sched="1f1b"),
    Strategy(dp_mode="hsdp", pp=2, microbatches=4, grad_accum=2,
             sched="1f1b", seq_parallel=False),
    Strategy(dp_mode="fsdp", pp=4, microbatches=8, sched="1f1b_i2"),
    Strategy(dp_mode="fsdp", pp=2, microbatches=8, sched="1f1b_i4",
             overlap=True),
    Strategy(dp_mode="fsdp", pp=2, microbatches=4, sched="zb"),
    Strategy(dp_mode="hsdp", tp=2, overlap=True),
])
def test_spec_round_trip(s):
    assert parse(s.format()) == s


def test_spec_defaults_and_aliases():
    assert parse("hsdp_tp4_cp1") == parse("hsdp_tp4")
    assert parse("hsdp") == Strategy()
    assert parse("fsdp_cp8").cp == 8
    assert parse("ddp").zero == 0
    assert parse("hsdp_tp4").zero == 3
    assert parse("fsdp_tp2_ctx").attn == "context"
    assert not parse("hsdp_nosp").seq_parallel


@pytest.mark.parametrize("bad", ["", "zorp_tp2", "hsdp_tp", "hsdp_xp4",
                                 "hsdp_tp4_tp8", "tp4", "fsdp_1f1b",
                                 "fsdp_pp2_mb4_1f1b_gpipe",
                                 "fsdp_zb",                  # sched w/o pp
                                 "fsdp_pp2_mb4_1f1b_i1",     # v must be >= 2
                                 "fsdp_pp2_mb4_i2",          # i<v> needs 1f1b
                                 "fsdp_pp4_mb6_1f1b_i2",     # mb % pp != 0
                                 "ddp_ovl",                  # ovl needs zero>=2
                                 "fsdp_z0_ovl"])
def test_spec_parse_rejects(bad):
    with pytest.raises(StrategyError):
        parse(bad)


def test_descriptor_validation():
    with pytest.raises(StrategyError):
        Strategy(tp=0)
    with pytest.raises(StrategyError):
        Strategy(dp_mode="zorp")
    with pytest.raises(StrategyError):
        Strategy(sched="interleaved")
    with pytest.raises(StrategyError):
        Strategy(sched="1f1b")        # sched token without a pipeline
    # tp and cp share the model axis
    with pytest.raises(StrategyError):
        Strategy(tp=2, cp=2).check(POD1)
    # a pipeline that cannot fill (mb < pp) is a construction error
    with pytest.raises(StrategyError):
        Strategy(pp=2)
    # pp > 1 lowers now (ISSUE 3): well-specified pipelines pass check
    Strategy(pp=2, microbatches=4).check(POD1)
    Strategy(pp=2, microbatches=4).check(POD1, LLAMA2_7B)
    assert not Strategy(tp=5).lowerable(POD1)       # 5 does not divide 256
    assert Strategy(tp=4).lowerable(POD1)
    # ISSUE 10 schedule-frontier degrees
    with pytest.raises(StrategyError):
        Strategy(sched="zb")                        # sched without a pipeline
    with pytest.raises(StrategyError):
        Strategy(pp=2, microbatches=4, sched="1f1b_i1")    # v >= 2
    with pytest.raises(StrategyError):
        Strategy(pp=4, microbatches=6, sched="1f1b_i2")    # mb % pp != 0
    with pytest.raises(StrategyError):
        Strategy(dp_mode="ddp", overlap=True)       # no sharded params
    # interleaving re-chunks the stack into pp*v slices: a 28-layer stack
    # splits over pp=4 stages (28 % 4 == 0) but not into 8 v-chunks
    Strategy(pp=2, microbatches=4, sched="1f1b_i2").check(POD1, LLAMA2_7B)
    Strategy(pp=2, microbatches=4, sched="zb").check(POD1, LLAMA2_7B)
    odd28 = dataclasses.replace(LLAMA2_7B, n_layers=28)
    Strategy(pp=4, microbatches=8, sched="1f1b").check(POD1, odd28)
    with pytest.raises(StrategyError):
        Strategy(pp=4, microbatches=8, sched="1f1b_i2").check(POD1, odd28)


def test_mb_lt_pp_is_error_not_silent_clamp():
    """Regression (descriptor.py): under-specified mb < pp used to be
    silently clamped to pp inside to_cost_strategy, so the cost model
    priced a pipeline the lowering would not run.  Now it is a
    StrategyError at validation time, and the analytic microbatch count
    is exactly the descriptor's."""
    with pytest.raises(StrategyError):
        parse("fsdp_pp4_mb2")
    with pytest.raises(StrategyError):
        Strategy(pp=4, microbatches=2)
    cost = Strategy(dp_mode="fsdp", pp=4, microbatches=16).to_cost_strategy(
        LLAMA2_7B, POD1)
    assert cost.microbatches == 16 and cost.pp == 4


def test_pp_model_constraints():
    """pp stages need a uniform layer stack; hybrids are rejected with
    cfg-aware validation (and still lower fine without pp).  MoE no
    longer blocks pp — the aux loss threads through the stage fn — but
    deepseek-moe's dense layer 0 breaks stack uniformity."""
    s = Strategy(dp_mode="fsdp", pp=2, microbatches=8)
    s.check(POD1, LLAMA2_7B)                      # uniform: ok
    jamba = get_config("jamba-v0.1-52b")
    with pytest.raises(StrategyError):
        s.check(POD1, jamba)                      # hybrid layer_plan
    assert Strategy(dp_mode="fsdp").lowerable(POD1, jamba)
    moe = get_config("deepseek-moe-16b")
    with pytest.raises(StrategyError):
        s.check(POD1, moe)                        # non-uniform (layer 0)
    uniform_moe = dataclasses.replace(
        moe, moe=dataclasses.replace(moe.moe, moe_start_layer=0))
    s.check(POD1, uniform_moe)                    # all-MoE stack: pp ok
    # layer count must split into contiguous stages
    odd = dataclasses.replace(LLAMA2_7B, n_layers=31)
    with pytest.raises(StrategyError):
        s.check(POD1, odd)


def test_ep_model_constraints():
    """ep needs an MoE config whose expert count it divides; ep stays
    inside the data axis.  ep x pp now composes (ISSUE 5): the expert
    all-to-all runs inside the pipeline stage body."""
    moe = get_config("deepseek-moe-16b")          # 64 routed experts
    Strategy(dp_mode="fsdp", ep=8).check(POD1, moe)
    with pytest.raises(StrategyError):
        Strategy(dp_mode="fsdp", ep=8).check(POD1, LLAMA2_7B)   # dense
    odd_e = dataclasses.replace(
        moe, moe=dataclasses.replace(moe.moe, n_experts=48))
    with pytest.raises(StrategyError):
        Strategy(dp_mode="fsdp", ep=32).check(POD1, odd_e)      # 48 % 32
    # ep x pp is a constructible, lowerable composition now — the old
    # StrategyError is gone (the uniform-stack rule still applies)
    uniform_moe = dataclasses.replace(
        moe, moe=dataclasses.replace(moe.moe, moe_start_layer=0))
    s = Strategy(dp_mode="fsdp", pp=2, ep=2, microbatches=8)
    s.check(POD1, uniform_moe)
    assert s.lowerable(POD1, uniform_moe)
    # hsdp: ep must divide the island-local data group
    assert Strategy(dp_mode="hsdp", ep=8).lowerable(POD2, moe)
    cost = Strategy(dp_mode="fsdp", ep=8).to_cost_strategy(moe, POD1)
    assert cost.ep == 8 and cost.dp % cost.ep == 0


# ---------------------------------------------------------------------------
# cost model <-> SPMD lowering agreement (the acceptance criterion)
# ---------------------------------------------------------------------------

def _agreement(cfg, topo, shape=TRAIN, **search_kw):
    ranked = search(cfg, topo, shape, require_fits=False, **search_kw)
    assert ranked, "planner returned no strategies"
    for p in ranked:
        s = p.strategy
        plan = s.to_plan(cfg, topo, shape, abstract=True)
        cost = s.to_cost_strategy(cfg, topo)
        # data-parallel group: batch axes of the mesh vs analytic dp
        # (the expert axis is part of the batch axes)
        assert plan.axis_size(plan.dp) == cost.dp, s.format()
        # model-parallel group: the mesh model axis vs tp*cp charged
        assert plan.tp_size == cost.tp * cost.cp, s.format()
        # pipeline stages: the mesh pipe axis vs the bubble term's P
        assert plan.pipe_size == cost.pp, s.format()
        # expert group: the mesh expert axis vs the a2a group charged
        assert plan.ep_size == cost.ep, s.format()
        # FSDP collective group: the axes params shard over vs the group
        # the cost model charges AllGather/ReduceScatter for
        fsdp_size = plan.axis_size(plan.fsdp)
        charged = cost.fsdp_n if cost.zero_stage >= 2 else 1
        assert max(fsdp_size, 1) == max(charged, 1), s.format()
        # and the cost report in the ranking priced this exact strategy
        assert p.report.strategy == cost, s.format()


def test_groups_agree_llama_pod():
    _agreement(LLAMA2_7B, POD1, cps=(1, 2, 4, 8), tps=(1, 2, 4, 8, 16))


def test_groups_agree_llama_pod_with_pp():
    _agreement(LLAMA2_7B, POD1, tps=(1, 2, 4), cps=(1, 2),
               pps=(1, 2, 4, 8))


def test_groups_agree_llama_multipod_hsdp():
    # pods=2 exercises the 'pod' axis: dp spans (pod, data), fsdp only data
    _agreement(LLAMA2_7B, POD2, dp_modes=("hsdp", "fsdp"),
               cps=(1, 2, 4), tps=(1, 4, 16))


def test_groups_agree_cp_gt_1_explicit():
    for spec in ("fsdp_cp2", "fsdp_cp4", "hsdp_cp8"):
        s = parse(spec)
        plan = s.to_plan(LLAMA2_7B, POD2, TRAIN, abstract=True)
        cost = s.to_cost_strategy(LLAMA2_7B, POD2)
        assert plan.attn == "context"
        assert cost.cp == s.cp and cost.tp == 1
        assert plan.tp_size == cost.cp
        assert plan.axis_size(plan.dp) == cost.dp


def test_context_fallback_charged_as_cp():
    """tp that can't shard heads lowers as context — and is priced as cp."""
    cfg = get_config("rwkv6-1.6b")
    hybrid = dataclasses.replace(cfg, attn_every=2)  # attention every 2nd
    # pick a tp that divides devices but not heads
    tp = 16
    while hybrid.n_heads % tp == 0:
        tp *= 2
    s = Strategy(dp_mode="fsdp", tp=tp)
    if not s.lowerable(POD1):
        pytest.skip("no viable non-dividing tp on this topology")
    assert s.resolved_attn(hybrid) == "context"
    cost = s.to_cost_strategy(hybrid, POD1)
    assert cost.cp == tp and cost.tp == 1


def test_hsdp_charges_island_group_and_cross_pod_ar():
    s = parse("hsdp_tp4")
    cost = s.to_cost_strategy(LLAMA2_7B, POD2)
    assert cost.fsdp_n == cost.dp // 2          # shard group inside the pod
    r = cm.step_time(LLAMA2_7B, POD2.hw, cost, 256, 4096,
                     hbm_capacity=POD2.hbm)
    assert r.comm_breakdown["hsdp_ar"] > 0      # cross-pod grad all-reduce
    fsdp_cost = parse("fsdp_tp4").to_cost_strategy(LLAMA2_7B, POD2)
    assert fsdp_cost.fsdp_n == fsdp_cost.dp
    r2 = cm.step_time(LLAMA2_7B, POD2.hw, fsdp_cost, 256, 4096,
                      hbm_capacity=POD2.hbm)
    assert r2.comm_breakdown["hsdp_ar"] == 0


# ---------------------------------------------------------------------------
# property tests (hypothesis; skip-stubbed when not installed)
# ---------------------------------------------------------------------------

def _strategy_kwargs():
    return dict(
        dp_mode=st.sampled_from(["hsdp", "fsdp", "ddp"]),
        tp=st.sampled_from([1, 2, 4, 8]),
        cp=st.sampled_from([1, 2, 4]),
        pp=st.sampled_from([1, 2, 4]),
        sched=st.sampled_from(["gpipe", "1f1b", "1f1b_i2", "zb"]),
        ep=st.sampled_from([1, 2, 4, 8]),
        zero_stage=st.sampled_from([None, 0, 2, 3]),
        microbatches=st.sampled_from([1, 4, 8, 16]),
        grad_accum=st.sampled_from([1, 2, 4]),
        attn=st.sampled_from([None, "head_tp", "context"]),
        seq_parallel=st.booleans(),
        overlap=st.booleans(),
    )


def _build(kw):
    try:
        return Strategy(**kw)
    except StrategyError:
        assume(False)


@settings(max_examples=200, deadline=None)
@given(st.fixed_dictionaries(_strategy_kwargs()))
def test_property_spec_round_trip(kw):
    """parse(format(s)) == s for every constructible strategy — including
    the pipeline-schedule token (ISSUE 5 satellite)."""
    s = _build(kw)
    assert parse(s.format()) == s
    # and format is canonical: a second round-trip is a fixed point
    assert parse(s.format()).format() == s.format()


@settings(max_examples=100, deadline=None)
@given(st.fixed_dictionaries(_strategy_kwargs()))
def test_property_group_sizes_match_mesh(kw):
    """For every valid strategy, the collective group sizes the cost model
    is charged equal the mesh axis sizes the lowering builds — dp, model,
    pipe, and (now) expert.  ep > 1 strategies validate against an MoE
    config (ep is rejected for dense models)."""
    s = _build(kw)
    cfg = get_config("deepseek-moe-16b") if kw["ep"] > 1 else LLAMA2_7B
    assume(s.lowerable(POD2, cfg))
    shape = ShapeConfig("prop", 4096,
                        max(256, s.grad_accum * s.microbatches), "train")
    try:
        plan = s.to_plan(cfg, POD2, shape, abstract=True)
        cost = s.to_cost_strategy(cfg, POD2)
    except StrategyError:
        assume(False)
    assert plan.axis_size(plan.dp) == cost.dp, s.format()
    assert plan.tp_size == cost.tp * cost.cp, s.format()
    assert plan.pipe_size == cost.pp, s.format()
    assert plan.ep_size == cost.ep, s.format()
    assert plan.microbatches == (s.microbatches if s.pp > 1 else 1)
    assert plan.pipe_sched == s.sched == cost.sched
    assert plan.zero_overlap == s.overlap == cost.overlap
    if s.ep > 1:
        assert plan.expert in plan.dp      # ep factored out of the data axes
        assert plan.axis_size(plan.dp) == s.dp_effective(POD2) * s.ep


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

def test_search_returns_lowerable_plans_on_host_mesh():
    """Every ranked strategy must actually lower on the host topology."""
    topo = strategy_lib.host_topology()
    cfg = reduced(get_config("qwen3-0.6b"))
    shape = ShapeConfig("host", 64, max(8, topo.n_devices), "train")
    ranked = search(cfg, topo, shape, cps=(1, 2, 4), tps=(1, 2, 4, 8))
    assert ranked
    for p in ranked:
        assert p.lowers
        plan = p.strategy.to_plan(cfg, topo, shape)   # real mesh, must build
        assert plan.mesh.devices.size == topo.n_devices
        # params of the reduced model shard without error
        pshapes = jax.eval_shape(
            lambda: __import__("repro.models.transformer",
                               fromlist=["init_params"]).init_params(
                                   cfg, jax.random.PRNGKey(0)))
        par.param_shardings(cfg, plan, pshapes)


def test_search_rank_and_objectives():
    ranked = search(LLAMA2_7B, POD1, TRAIN, cps=(1, 2, 4))
    scores = [p.score for p in ranked]
    assert scores == sorted(scores, reverse=True)
    assert all(p.report.fits for p in ranked)    # fits-filter applied
    by_energy = search(LLAMA2_7B, POD1, TRAIN, objective="tokens_per_joule")
    assert by_energy[0].report.tokens_per_joule >= \
        by_energy[-1].report.tokens_per_joule
    with pytest.raises(StrategyError):
        search(LLAMA2_7B, POD1, TRAIN, objective="vibes")


def test_search_sweeps_cp_degrees():
    ranked = search(LLAMA2_7B, POD1, TRAIN, cps=(1, 2, 4, 8),
                    require_fits=False)
    assert any(p.strategy.cp > 1 for p in ranked)


def test_search_returns_pp_candidates_by_default():
    """The planner no longer filters pipeline parallelism out of the
    default sweep: pp>1 candidates are ranked and lowerable."""
    ranked = search(LLAMA2_7B, POD1, TRAIN, require_fits=False)
    pp = [p for p in ranked if p.strategy.pp > 1]
    assert pp, "no pp>1 strategies in the default sweep"
    for p in pp:
        assert p.lowers
        assert p.strategy.microbatches >= p.strategy.pp
        plan = p.strategy.to_plan(LLAMA2_7B, POD1, TRAIN, abstract=True)
        assert plan.pipe_size == p.strategy.pp


def test_pp_on_pareto_front_when_node_bandwidth_constrained():
    """The paper's headline crossover: once inter-island bandwidth is
    starved, pipeline parallelism overtakes pure sharded-DP — the planner
    must surface it, not just price it."""
    slow = dataclasses.replace(cm.H100, inter_bw=25e9, alpha_inter=25e-6)
    topo = Topology("slow-fabric", 256, island=8, hardware="H100",
                    hbm=80e9, hw_obj=slow)
    ranked = search(LLAMA2_7B, topo, TRAIN, require_fits=False)
    assert any(p.strategy.pp > 1 for p in ranked)
    front = pareto_front(ranked, objectives=("wps", "tokens_per_joule"))
    assert any(p.strategy.pp > 1 for p in front), \
        [p.spec for p in front]
    # and the pp winner actually beats the best pp=1 point on wps
    best_pp = max(p.score for p in ranked if p.strategy.pp > 1)
    best_flat = max(p.score for p in ranked if p.strategy.pp == 1)
    assert best_pp > best_flat


def test_1f1b_memory_flips_fits_in_planner_sweep():
    """ISSUE 5 acceptance (pinned): the planner sweeps schedules by
    default, and there is a topology where 1F1B's smaller in-flight
    activation footprint flips ``fits`` relative to the same-mesh GPipe
    point — i.e. the schedule choice changes which strategies are
    feasible, exactly the memory-forces-strategy-changes effect the
    paper models."""
    s_g = Strategy(dp_mode="fsdp", pp=4, microbatches=16)
    s_f = dataclasses.replace(s_g, sched="1f1b")
    # long sequences make activations dominate; pick hbm between the two
    # schedules' predicted footprints so the flip is by construction
    shape = ShapeConfig("flip", 16384, 256, "train")
    base = Topology("flip", 256, island=8, hardware="H100", hbm=80e9)
    mem = {s.sched: strategy_lib.evaluate(LLAMA2_7B, s, base, shape)
           .memory_per_device for s in (s_g, s_f)}
    assert mem["1f1b"] < mem["gpipe"]
    topo = dataclasses.replace(base, hbm=(mem["1f1b"] + mem["gpipe"]) / 2)
    r_g = strategy_lib.evaluate(LLAMA2_7B, s_g, topo, shape)
    r_f = strategy_lib.evaluate(LLAMA2_7B, s_f, topo, shape)
    assert r_f.fits and not r_g.fits
    # and the default planner sweep surfaces the 1f1b point as fitting
    # while its gpipe twin is excluded by the fits filter
    ranked = search(LLAMA2_7B, topo, shape, microbatches=16,
                    dp_modes=("fsdp",))
    specs = {p.spec for p in ranked}
    assert s_f.format() in specs, sorted(specs)
    assert s_g.format() not in specs
    assert all(p.report.fits for p in ranked)


def test_overlap_token_flips_fsdp_frontier():
    """ISSUE 10 acceptance (pinned): on an FSDP-bound A100 pod the
    planner's top strategy *changes* when the gather/compute overlap
    token enters the sweep.  Without it, exposed per-layer parameter
    gathers push the winner to tp=2 (smaller gather group per shard);
    with it, the prefetch window hides the gathers and plain fsdp+ovl
    overtakes — the overlap degree moves the frontier, not just a
    number."""
    cfg = get_config("llama2-70b")
    topo = Topology("a100-1024", 1024, island=8, hardware="A100", hbm=80e9)
    shape = ShapeConfig("ovl-flip", 4096, 1024, "train")
    kw = dict(require_lowerable=False, dp_modes=("fsdp",),
              zero_stages=(3,), precisions=("bf16",))
    off = search(cfg, topo, shape, overlaps=(False,), **kw)
    both = search(cfg, topo, shape, **kw)
    assert off[0].spec == "fsdp_tp2_z3_bf16", off[0].spec
    assert both[0].spec == "fsdp_z3_ovl_bf16", both[0].spec
    assert both[0].report.wps > off[0].report.wps
    # the same mesh without the token is strictly slower in the ranking
    by_spec = {p.spec: p for p in both}
    assert by_spec["fsdp_z3_ovl_bf16"].report.t_step < \
        by_spec["fsdp_z3_bf16"].report.t_step


def test_pareto_front_subset_and_contains_best():
    ranked = search(LLAMA2_7B, POD1, TRAIN, require_fits=False)
    front = pareto_front(ranked, objectives=("wps", "tokens_per_joule"))
    specs = {p.spec for p in ranked}
    assert front and {p.spec for p in front} <= specs
    assert ranked[0].spec in {p.spec for p in front}  # wps-best not dominated


def test_resolve_auto_and_spec():
    s, planned = strategy_lib.resolve("auto", LLAMA2_7B, POD1, TRAIN)
    assert planned is not None and planned.strategy == s
    s2, planned2 = strategy_lib.resolve("hsdp_tp4", LLAMA2_7B, POD1, TRAIN)
    assert planned2 is None and s2.tp == 4
    with pytest.raises(StrategyError):
        strategy_lib.resolve("hsdp_tp5", LLAMA2_7B, POD1, TRAIN)


def test_deprecated_shims_removed():
    """ROADMAP: 'remove once no caller remains' — the deprecated
    sweep_strategies/best_strategy and parallel.choose_plan shims are
    gone; the planner is the only strategy-sweep surface."""
    from repro.core import parallel as par_mod
    assert not hasattr(cm, "sweep_strategies")
    assert not hasattr(cm, "best_strategy")
    assert not hasattr(par_mod, "choose_plan")


# ---------------------------------------------------------------------------
# topology / mesh building
# ---------------------------------------------------------------------------

def test_build_mesh_topology_parameterized():
    topo = Topology("t", 512, island=256, hardware="TPUv5e", hbm=16e9)
    m = strategy_lib.build_mesh(topo, model=16, pods=2, abstract=True)
    assert dict(m.shape) == {"pod": 2, "data": 16, "model": 16}
    m1 = strategy_lib.build_mesh(POD1, model=16, abstract=True)
    assert dict(m1.shape) == {"data": 16, "model": 16}
    with pytest.raises(ValueError):
        strategy_lib.build_mesh(POD1, model=5)


def test_get_topology_names():
    assert strategy_lib.get_topology("pod").n_devices == 256
    assert strategy_lib.get_topology("multipod").n_devices == 512
    assert strategy_lib.get_topology("multipod4").n_devices == 1024
    assert strategy_lib.get_topology("host").n_devices == len(jax.devices())
    with pytest.raises(ValueError):
        strategy_lib.get_topology("cluster9000")


def test_build_mesh_axes_are_auto():
    """GSPMD plans need Auto axes: under Explicit ones a sharding
    constraint is an assertion and the embedding gather cannot resolve."""
    from jax.sharding import AxisType
    topo = strategy_lib.host_topology(n_devices=1)
    for abstract in (False, True):
        m = strategy_lib.build_mesh(topo, abstract=abstract)
        assert set(m.axis_types) == {AxisType.Auto}


class _StubDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_host_topology_reads_device_kind(monkeypatch):
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_StubDevice("TPU v5 lite")] * 4)
    topo = strategy_lib.host_topology()
    assert (topo.hardware, topo.hbm, topo.n_devices) == ("TPUv5e", 16e9, 4)
    assert topo.hw is cm.TPU_V5E
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_StubDevice("TPU v99 mystery")])
    with pytest.raises(ValueError, match="TPU v99 mystery"):
        strategy_lib.host_topology()


def test_host_topology_cpu_keeps_named_profile():
    topo = strategy_lib.host_topology()
    assert (topo.hardware, topo.hbm) == ("H100", 80e9)


def test_compile_cache_dir_honours_env(monkeypatch):
    from repro.launch import devices
    from repro.perf.paths import REPO_ROOT
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(devices.CACHE_ENV, "/elsewhere/cache")
    assert devices.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was     # JAX reads env
    monkeypatch.delenv(devices.CACHE_ENV)
    fixed = devices.compile_cache_dir()
    assert fixed == f"{REPO_ROOT}/.jax_cache"
    try:
        assert devices.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_decode_cache_axes_long_context():
    s = parse("hsdp_tp16")
    plan = s.to_plan(get_config("qwen3-0.6b"), POD1, SHAPES["long_500k"],
                     abstract=True)
    assert plan.decode_cache_axes == ("data", "model")
