"""Pipeline *schedule* subsystem tests (ISSUE 5): tick-table simulations
must reproduce the analytic bubble/memory formulas, the 1F1B custom-vjp
execution must match the sequential oracle (forward AND gradient) on the
shared 8-virtual-device fixture."""
import logging

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, reduced
from repro.core.compat import make_mesh, use_mesh
from repro.core.pipeline import (SCHEDULES, _ring_depths, batch_axes_spec,
                                 bubble_fraction, get_schedule,
                                 inflight_microbatches, known_schedule,
                                 make_pipelined_block_fn, op_tick_counts,
                                 parse_schedule, pipeline_apply,
                                 virtual_stages)
from repro.models.layers import Runtime
from repro.models.transformer import (_apply_layer, _init_layer, _sig,
                                      _tree_stack)


# ---------------------------------------------------------------------------
# tick-table simulation vs analytic formulas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sched", ["gpipe", "1f1b"])
@pytest.mark.parametrize("P_,M", [(2, 2), (2, 8), (4, 4), (4, 8), (4, 13),
                                  (8, 8), (8, 32)])
def test_tick_table_matches_formulas(sched, P_, M):
    """The executable loops are index arithmetic over exactly these
    tables: counted idle fraction == bubble_fraction, counted peak
    in-flight == inflight_microbatches."""
    sim = get_schedule(sched).simulate(P_, M)
    assert sim["bubble"] == pytest.approx(bubble_fraction(P_, M, sched))
    assert sim["peak_inflight"] == inflight_microbatches(P_, M, sched)


@pytest.mark.parametrize("P_,M", [(2, 4), (4, 8)])
def test_tick_table_well_formed(P_, M):
    """Every microbatch is forwarded and backwarded exactly once per
    stage, in order, and 1F1B's combined table is 2(M+P-1) ticks."""
    for sched, want_ticks in (("gpipe", 2 * (M + P_ - 1)),
                              ("1f1b", 2 * (M + P_ - 1))):
        table = get_schedule(sched).tick_table(P_, M)
        assert len(table) == want_ticks
        for s in range(P_):
            fs = [j for op, j in (row[s] for row in table) if op == "F"]
            bs = [j for op, j in (row[s] for row in table) if op == "B"]
            assert fs == list(range(M)), (sched, s)
            assert sorted(bs) == list(range(M)), (sched, s)


def test_1f1b_inflight_strictly_smaller_than_gpipe():
    assert inflight_microbatches(4, 16, "1f1b") == 4
    assert inflight_microbatches(4, 16, "gpipe") == 16
    assert inflight_microbatches(4, 4, "1f1b") == 4
    assert bubble_fraction(4, 16, "1f1b") == bubble_fraction(4, 16, "gpipe")


def test_1f1b_rejects_underfilled_pipeline():
    with pytest.raises(ValueError):
        get_schedule("1f1b").tick_table(4, 2)
    with pytest.raises(ValueError):
        get_schedule("unknown")
    with pytest.raises(ValueError):
        bubble_fraction(2, 8, "interleaved")


@pytest.mark.parametrize("P_,M", [(2, 2), (2, 3), (2, 4), (4, 4), (4, 5),
                                  (4, 8)])
def test_1f1b_table_holds_min_m_p_inputs(P_, M):
    """1F1B runs through the shared table executor: its table places
    stage s's forward of microbatch j at s + j in warmup (j < P - s), else
    2j + s, and the backward at 2j + 2P - 1 - s; sized from that table,
    the executor's rings hold min(M, P) stage inputs and one inbound
    activation, cotangent and wgrad slot each."""
    table = get_schedule("1f1b")._full_table(P_, M)
    assert len(table) == 2 * (M + P_ - 1)
    for s in range(P_):
        for j in range(M):
            f = s + j if j < P_ - s else 2 * j + s
            assert table[f][s] == ("F", 0, j), (s, j)
            assert table[2 * j + 2 * P_ - 1 - s][s] == ("B", 0, j), (s, j)
    assert _ring_depths(table, P_, M, 1) == (min(M, P_), 1, 1, 1)


# ---------------------------------------------------------------------------
# schedule frontier (ISSUE 10): interleaved 1f1b_i<v> and zero-bubble zb
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sched", ["1f1b_i2", "1f1b_i3", "zb"])
@pytest.mark.parametrize("P_,M", [(2, 4), (4, 8), (4, 16), (8, 16)])
def test_frontier_tick_tables_match_formulas(sched, P_, M):
    """Same contract the gpipe/1f1b tables honour: the greedy list
    scheduler's counted idle fraction and peak in-flight must equal the
    analytic bubble_fraction / inflight_microbatches terms the cost
    model charges."""
    sim = get_schedule(sched).simulate(P_, M)
    assert sim["bubble"] == pytest.approx(bubble_fraction(P_, M, sched))
    assert sim["peak_inflight"] == inflight_microbatches(P_, M, sched)


def test_schedule_grammar():
    """'1f1b_i<v>' parses as v virtual stages per rank; 'zb' is a known
    one-chunk schedule; junk and v=1 are rejected with ValueError."""
    assert parse_schedule("zb") == ("zb", 1)
    assert parse_schedule("1f1b_i2")[1] == 2
    assert virtual_stages("1f1b_i4") == 4
    assert virtual_stages("gpipe") == 1 and virtual_stages("zb") == 1
    assert known_schedule("1f1b_i7") and known_schedule("zb")
    assert not known_schedule("interleaved") and not known_schedule("1f1b_i1")
    with pytest.raises(ValueError):
        parse_schedule("1f1b_i1")     # v == 1 is plain 1f1b
    with pytest.raises(ValueError):
        parse_schedule("zb_i2")


def test_frontier_schedule_rejections():
    with pytest.raises(ValueError):
        get_schedule("1f1b_i2").tick_table(4, 6)   # M % P != 0
    with pytest.raises(ValueError):
        get_schedule("zb").tick_table(4, 2)        # M < P


def test_zb_op_tick_counts():
    """zb splits every backward into dgrad (B) + wgrad (W) sub-ticks:
    P*M of each op, and the total tick span is 3M + 2(P-1)."""
    c = op_tick_counts("zb", 4, 8)
    assert c["F"] == c["B"] == c["W"] == 32
    assert c["ticks"] == 3 * 8 + 2 * (4 - 1)
    c1 = op_tick_counts("1f1b", 4, 8)
    assert c1["W"] == 0 and c1["F"] == c1["B"] == 32
    ci = op_tick_counts("1f1b_i2", 4, 8)
    assert ci["W"] == 0 and ci["F"] == ci["B"] == 64   # per-chunk ticks


@settings(max_examples=40, deadline=None)
@given(P_=st.integers(2, 5), k=st.integers(1, 5), v=st.integers(2, 3))
def test_property_interleaved_bubble_formula_vs_simulation(P_, k, v):
    """ISSUE 10 satellite: for every (P, M = kP, v) the interleaved
    bubble formula (P-1)/(vM+P-1) equals the tick-count simulation —
    the v-times-finer warmup ramp is exactly what the table emits."""
    M = P_ * k
    sim = get_schedule(f"1f1b_i{v}").simulate(P_, M)
    assert sim["bubble"] == pytest.approx((P_ - 1) / (v * M + P_ - 1))
    assert sim["bubble"] < bubble_fraction(P_, M, "1f1b")


@settings(max_examples=40, deadline=None)
@given(P_=st.integers(2, 6), extra=st.integers(0, 16))
def test_property_zb_bubble_and_inflight_vs_1f1b(P_, extra):
    """ISSUE 10 satellite: zb's simulated bubble matches
    2(P-1)/(3M+2P-2), stays below 1F1B's, and its activation peak never
    exceeds 1F1B's min(M, P) cap (the dgrad sub-tick frees the
    activation; only the param-shaped wgrad stash persists)."""
    M = P_ + extra
    zb = get_schedule("zb").simulate(P_, M)
    fb = get_schedule("1f1b").simulate(P_, M)
    assert zb["bubble"] == pytest.approx(
        2 * (P_ - 1) / (3 * M + 2 * P_ - 2))
    assert zb["bubble"] < fb["bubble"]
    assert zb["peak_inflight"] <= fb["peak_inflight"]


@settings(max_examples=60, deadline=None)
@given(P_=st.integers(2, 6), extra=st.integers(0, 24))
def test_property_1f1b_bubble_formula_vs_simulation(P_, extra):
    """ISSUE 5 satellite: the 1F1B bubble formula equals the tick-count
    simulation for every (P, M >= P), and the simulated in-flight peak is
    exactly min(M, P)."""
    M = P_ + extra
    sim = get_schedule("1f1b").simulate(P_, M)
    assert sim["bubble"] == pytest.approx((P_ - 1) / (M + P_ - 1))
    assert sim["peak_inflight"] == min(M, P_)
    gsim = get_schedule("gpipe").simulate(P_, M)
    assert gsim["peak_inflight"] == M
    assert gsim["bubble"] == pytest.approx(sim["bubble"])


# ---------------------------------------------------------------------------
# 1F1B execution == sequential oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen3-0.6b"), n_layers=4, d_model=128)
    rt = Runtime()
    key = jax.random.PRNGKey(0)
    layers = [_init_layer(cfg, i, k) for i, k in
              enumerate(jax.random.split(key, 4))]
    stacked = {"layers": _tree_stack(layers)}
    return cfg, rt, layers, stacked


def _sequential(cfg, rt, layers, x):
    M, mb, S, d = x.shape
    h = x.reshape(M * mb, S, d)
    for lp in layers:
        h, _, _ = _apply_layer(cfg, _sig(cfg, 0), lp, h, None, rt)
    return h.reshape(M, mb, S, d)


@pytest.mark.parametrize("mesh_axes", [("pipe",), ("pipe", "data")])
def test_1f1b_matches_sequential_fwd_and_grad(setup, eight_devices,
                                              mesh_axes):
    """The 1F1B custom_vjp (combined recompute-fwd/bwd tick loop) must
    agree with sequential application — including the composed
    (pipe, data) mesh and gradients w.r.t. params AND inputs."""
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
                                   batch_axes=batch_axes, schedule="1f1b")
        return out

    with use_mesh(mesh):
        out_p = jax.jit(pipelined)(stacked, x)
    out_s = _sequential(cfg, rt, layers, x)
    assert float(jnp.max(jnp.abs(out_p - out_s))) < 1e-4

    def loss_p(params, x):
        return jnp.sum(pipelined(params, x) ** 2)

    def loss_s(layers, x):
        return jnp.sum(_sequential(cfg, rt, layers, x) ** 2)

    with use_mesh(mesh):
        g_p, gx_p = jax.jit(jax.grad(loss_p, argnums=(0, 1)))(stacked, x)
    g_s_layers, gx_s = jax.grad(loss_s, argnums=(0, 1))(layers, x)
    g_s = {"layers": _tree_stack(g_s_layers)}
    errs = [float(jnp.max(jnp.abs(a - b))) for a, b in
            zip(jax.tree.leaves(g_p), jax.tree.leaves(g_s))]
    assert max(errs) < 5e-3, max(errs)
    assert float(jnp.max(jnp.abs(gx_p - gx_s))) < 5e-3


def test_all_schedules_equal_gpipe_execution(setup, eight_devices):
    """Same work, different order: every registered schedule (plus an
    unregistered interleave depth) computes the identical function, so
    outputs and grads must agree with gpipe's — including the zb
    executor's split dgrad/wgrad backward and the interleaved
    non-contiguous stage chunking (L=4 % (P=2 * v=2) == 0)."""
    cfg, rt, layers, stacked = setup
    mesh = make_mesh((2,), ("pipe",), devices=eight_devices[:2])
    M, mb, S, d = 4, 2, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, S, d)) * 0.5
    stage_fn = make_pipelined_block_fn(cfg, rt)

    outs, grads = {}, {}
    for sched in ("gpipe", "1f1b", "1f1b_i2", "zb"):
        def loss(params, sched=sched):
            out, _ = pipeline_apply(stage_fn, params, x, mesh, "pipe",
                                    schedule=sched)
            return jnp.sum(out ** 2)

        with use_mesh(mesh):
            outs[sched], grads[sched] = jax.jit(
                jax.value_and_grad(loss))(stacked)
    for sched in ("1f1b", "1f1b_i2", "zb"):
        assert float(outs["gpipe"]) == pytest.approx(float(outs[sched]),
                                                     rel=1e-5), sched
        errs = [float(jnp.max(jnp.abs(a - b))) for a, b in
                zip(jax.tree.leaves(grads["gpipe"]),
                    jax.tree.leaves(grads[sched]))]
        assert max(errs) < 5e-3, (sched, max(errs))


@pytest.mark.parametrize("sched", ["1f1b_i2", "zb"])
def test_frontier_schedules_match_sequential_composed_mesh(
        setup, eight_devices, sched):
    """ISSUE 10 acceptance: the new executors must agree with sequential
    application on a composed (pipe, data) mesh — forward AND gradients
    w.r.t. params and inputs, with the interleaved param permutation
    un-permuting its cotangents."""
    cfg, rt, layers, stacked = setup
    mesh = make_mesh((2, 2), ("pipe", "data"), devices=eight_devices[:4])
    M, mb, S, d = 4, 2, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(3), (M, mb, S, d)) * 0.5
    stage_fn = make_pipelined_block_fn(cfg, rt)

    def pipelined(params, x):
        out, _aux = pipeline_apply(stage_fn, params, x, mesh, "pipe",
                                   batch_axes=("data",), schedule=sched)
        return out

    with use_mesh(mesh):
        out_p = jax.jit(pipelined)(stacked, x)
    out_s = _sequential(cfg, rt, layers, x)
    assert float(jnp.max(jnp.abs(out_p - out_s))) < 1e-4

    def loss_p(params, x):
        return jnp.sum(pipelined(params, x) ** 2)

    def loss_s(layers, x):
        return jnp.sum(_sequential(cfg, rt, layers, x) ** 2)

    with use_mesh(mesh):
        g_p, gx_p = jax.jit(jax.grad(loss_p, argnums=(0, 1)))(stacked, x)
    g_s_layers, gx_s = jax.grad(loss_s, argnums=(0, 1))(layers, x)
    g_s = {"layers": _tree_stack(g_s_layers)}
    errs = [float(jnp.max(jnp.abs(a - b))) for a, b in
            zip(jax.tree.leaves(g_p), jax.tree.leaves(g_s))]
    assert max(errs) < 5e-3, max(errs)
    assert float(jnp.max(jnp.abs(gx_p - gx_s))) < 5e-3


def test_interleaved_apply_rejects_bad_chunking(setup, eight_devices):
    """L % (P*v) != 0 and M % P != 0 are construction errors, not silent
    truncation."""
    cfg, rt, layers, stacked = setup
    mesh = make_mesh((4,), ("pipe",), devices=eight_devices[:4])
    stage_fn = make_pipelined_block_fn(cfg, rt)
    x = jnp.zeros((8, 2, 16, cfg.d_model))
    with pytest.raises(ValueError):       # 4 layers % (4 stages * 2) != 0
        with use_mesh(mesh):
            pipeline_apply(stage_fn, stacked, x, mesh, "pipe",
                           schedule="1f1b_i2")
    mesh2 = make_mesh((2,), ("pipe",), devices=eight_devices[:2])
    x2 = jnp.zeros((3, 2, 16, cfg.d_model))
    with pytest.raises(ValueError):       # M=3 % P=2 != 0
        with use_mesh(mesh2):
            pipeline_apply(stage_fn, stacked, x2, mesh2, "pipe",
                           schedule="1f1b_i2")


def test_measured_memory_ordering_gpipe_vs_1f1b(setup, eight_devices):
    """ISSUE 10 satellite: the compiled executable's measured temp
    (activation/workspace) bytes must order the same way the cost
    model's in-flight term predicts — gpipe holds all M=8 microbatch
    activations, 1f1b caps at P=4."""
    cfg, rt, layers, stacked = setup
    mesh = make_mesh((4,), ("pipe",), devices=eight_devices[:4])
    M, mb, S, d = 8, 2, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(4), (M, mb, S, d)) * 0.5
    stage_fn = make_pipelined_block_fn(cfg, rt)
    temp = {}
    for sched in ("gpipe", "1f1b"):
        def loss(params, sched=sched):
            out, _ = pipeline_apply(stage_fn, params, x, mesh, "pipe",
                                    schedule=sched)
            return jnp.sum(out ** 2)

        with use_mesh(mesh):
            compiled = jax.jit(jax.value_and_grad(loss)).lower(
                stacked).compile()
        ma = compiled.memory_analysis()
        if ma is None or not getattr(ma, "temp_size_in_bytes", 0):
            pytest.skip("backend reports no executable memory analysis")
        temp[sched] = int(ma.temp_size_in_bytes)
    assert inflight_microbatches(4, M, "1f1b") < \
        inflight_microbatches(4, M, "gpipe")
    assert temp["1f1b"] < temp["gpipe"], temp


def test_1f1b_apply_rejects_underfilled(setup, eight_devices):
    cfg, rt, layers, stacked = setup
    mesh = make_mesh((4,), ("pipe",), devices=eight_devices[:4])
    x = jnp.zeros((2, 2, 16, cfg.d_model))       # M=2 < P=4
    stage_fn = make_pipelined_block_fn(cfg, rt)
    with pytest.raises(ValueError):
        with use_mesh(mesh):
            pipeline_apply(stage_fn, stacked, x, mesh, "pipe",
                           schedule="1f1b")


# ---------------------------------------------------------------------------
# batch-axis drop warning
# ---------------------------------------------------------------------------

def test_batch_axes_spec_warns_once_on_dropped_axis(eight_devices, caplog):
    """pp with microbatch rows that cannot occupy the data axis runs with
    replicated (redundant) data-parallel compute; that used to be fully
    silent — now it logs a warning, once per configuration."""
    import repro.core.pipeline as pl
    mesh = make_mesh((2, 4), ("pipe", "data"), devices=eight_devices)
    pl._warned_dropped.clear()
    with caplog.at_level(logging.WARNING, logger="repro.core.pipeline"):
        kept = batch_axes_spec(mesh, ("data",), 3)      # 3 % 4 -> dropped
        assert kept == ()
        n1 = sum("replicated" in r.message for r in caplog.records)
        kept = batch_axes_spec(mesh, ("data",), 3)      # same config again
        n2 = sum("replicated" in r.message for r in caplog.records)
    assert n1 == 1 and n2 == 1                           # warned exactly once
    with caplog.at_level(logging.WARNING, logger="repro.core.pipeline"):
        caplog.clear()
        assert batch_axes_spec(mesh, ("data",), 8) == ("data",)
        assert not caplog.records                        # clean fit: silent


def test_schedule_registry():
    assert set(SCHEDULES) == {"gpipe", "1f1b", "1f1b_i2", "zb"}
    for name, sched in SCHEDULES.items():
        assert sched.name == name
        assert get_schedule(name) is sched
    # unregistered interleave depths resolve through the grammar
    assert get_schedule("1f1b_i3").name == "1f1b_i3"
