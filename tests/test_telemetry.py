"""Telemetry subsystem: spans/metrics/sinks, Chrome-trace export, drift
monitor math, serve latency accounting, supervisor event-log migration,
and the train-CLI trace smoke (acceptance: per-step spans sum to within
10% of wall-clock step time)."""
import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro import telemetry as tel


class FakeClock:
    def __init__(self, t=10.0):
        # starts nonzero: lifecycle code treats t == 0.0 as "not reached"
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def make_recorder(clk=None):
    clk = clk or FakeClock()
    mem = tel.InMemorySink()
    rec = tel.Recorder(sinks=[mem], clock=clk, annotate_jax=False)
    return rec, mem, clk


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_depth_parent_and_timing():
    rec, mem, clk = make_recorder()
    with rec.span("outer"):
        clk.advance(1.0)
        with rec.span("inner"):
            clk.advance(0.25)
        clk.advance(0.5)
    spans = mem.by_kind("span")
    # children close (and emit) before parents
    assert [s["name"] for s in spans] == ["inner", "outer"]
    inner, outer = spans
    assert inner["depth"] == 1 and inner["parent"] == "outer"
    assert outer["depth"] == 0 and "parent" not in outer
    assert inner["dur"] == pytest.approx(0.25)
    assert outer["dur"] == pytest.approx(1.75)
    assert outer["ts"] == pytest.approx(10.0)
    assert inner["ts"] == pytest.approx(11.0)


def test_span_emitted_on_exception_and_stack_unwinds():
    rec, mem, clk = make_recorder()
    with pytest.raises(ValueError):
        with rec.span("boom"):
            clk.advance(0.5)
            raise ValueError("x")
    (span,) = mem.by_kind("span")
    assert span["name"] == "boom" and span["dur"] == pytest.approx(0.5)
    # the thread-local stack unwound: a new span is top-level again
    with rec.span("after"):
        pass
    assert mem.by_name("after")[0]["depth"] == 0


def test_span_attrs_mutable_during_block():
    rec, mem, _ = make_recorder()
    with rec.span("s", static=1) as attrs:
        attrs["tokens"] = 128
    (span,) = mem.by_kind("span")
    assert span["attrs"] == {"static": 1, "tokens": 128}


def test_span_thread_local_nesting():
    rec, mem, _ = make_recorder()
    done = threading.Event()

    def worker():
        with rec.span("t2"):
            done.wait(5)

    t = threading.Thread(target=worker)
    with rec.span("t1-outer"):
        t.start()
        # the other thread's open span must not become our parent
        with rec.span("t1-inner"):
            pass
        done.set()
    t.join()
    inner = mem.by_name("t1-inner")[0]
    assert inner["parent"] == "t1-outer" and inner["depth"] == 1
    assert mem.by_name("t2")[0]["depth"] == 0


def test_null_recorder_is_inert():
    with tel.NULL.span("x") as attrs:
        assert attrs == {}
    tel.NULL.counter("c")
    tel.NULL.gauge("g", 1.0)
    tel.NULL.observe("h", 1.0)
    assert tel.NULL.metrics.snapshot() == {}
    with pytest.raises(RuntimeError):
        tel.NULL.add_sink(tel.InMemorySink())


# ---------------------------------------------------------------------------
# metrics: exactness vs sorted-list oracle
# ---------------------------------------------------------------------------

def _oracle_percentile(values, q):
    s = sorted(values)
    if q <= 0:
        return s[0]
    return s[max(math.ceil(q / 100.0 * len(s)), 1) - 1]


@pytest.mark.parametrize("n", [1, 2, 5, 100, 997])
@pytest.mark.parametrize("q", [0, 1, 50, 90, 99, 100])
def test_histogram_percentiles_exact_vs_oracle(n, q):
    rng = np.random.default_rng(n * 1000 + q)
    values = rng.lognormal(mean=-3, sigma=2, size=n).tolist()
    h = tel.Histogram("h")
    for v in values:
        h.observe(v)
    assert h.percentile(q) == _oracle_percentile(values, q)
    # nearest-rank percentiles are actual observations, never interpolants
    assert h.percentile(q) in values


def test_histogram_bucket_counts_and_snapshot():
    h = tel.Histogram("h", buckets=[0.1, 1.0, 10.0])
    for v in [0.05, 0.5, 0.5, 5.0, 50.0]:
        h.observe(v)
    assert h.bucket_counts == [1, 2, 1, 1]     # <=0.1, <=1, <=10, +inf
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(56.05)
    assert snap["p50"] == 0.5
    assert snap["buckets"] == {"0.1": 1, "1.0": 2, "10.0": 1, "inf": 1}


def test_histogram_weighted_observe():
    h = tel.Histogram("h")
    h.observe(2.0, n=3)
    assert h.count == 3 and h.sum == pytest.approx(6.0)
    assert h.percentile(99) == 2.0


def test_registry_snapshot_and_type_guard():
    reg = tel.MetricsRegistry()
    reg.counter("c").inc(2)
    reg.gauge("g").set(1.5)
    snap = reg.snapshot()
    assert snap["c"] == {"type": "counter", "value": 2}
    assert snap["g"] == {"type": "gauge", "value": 1.5}
    with pytest.raises(TypeError):
        reg.gauge("c")


def test_percentile_empty_raises():
    with pytest.raises(ValueError):
        tel.percentile([], 50)


# ---------------------------------------------------------------------------
# event schema + sinks
# ---------------------------------------------------------------------------

def test_event_schema_validation():
    ok = tel.make_event("gauge", "g", 1.0, value=2.0)
    assert tel.validate_event(ok) == []
    assert tel.validate_event({"kind": "gauge"})          # missing fields
    assert tel.validate_event({"ts": 0, "kind": "span", "name": "s",
                               "dur": -1})                # negative dur
    assert tel.validate_event({"ts": 0, "kind": "nope", "name": "s"})
    assert tel.validate_event([1, 2])
    with pytest.raises(ValueError):
        tel.make_event("span", "s", 0.0)                  # span needs dur


def test_jsonl_sink_roundtrip_validates(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    rec = tel.Recorder(sinks=[tel.JsonlSink(path)], clock=FakeClock(),
                       annotate_jax=False)
    with rec.span("s"):
        rec.counter("c")
        rec.gauge("g", 1.0)
        rec.observe("h", 0.5)
    rec.event("e", why="test")
    rec.close()
    n, errs = tel.validate_jsonl(path)
    assert n == 5 and errs == []
    kinds = [json.loads(l)["kind"] for l in open(path)]
    assert sorted(kinds) == ["counter", "event", "gauge", "histogram",
                             "span"]


def test_schema_check_cli(tmp_path):
    good = tmp_path / "good.jsonl"
    good.write_text(json.dumps(tel.make_event("gauge", "g", 1.0,
                                              value=2.0)) + "\n")
    from repro.telemetry.__main__ import main as check_main
    assert check_main([str(good)]) == 0
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "gauge"}\n')
    assert check_main([str(bad)]) == 1
    assert check_main([str(tmp_path)]) == 1      # dir scan finds bad too
    assert check_main([str(tmp_path / "missing.jsonl")]) == 1


# ---------------------------------------------------------------------------
# Chrome trace / Perfetto export
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_and_units(tmp_path):
    path = str(tmp_path / "trace.json")
    clk = FakeClock()
    rec = tel.Recorder(sinks=[tel.ChromeTraceSink(path)], clock=clk,
                       annotate_jax=False)
    with rec.span("step", step_num=3):
        clk.advance(0.002)
    rec.gauge("wps", 1000.0)
    rec.close()
    n, errs = tel.validate_chrome_trace(path)
    assert errs == [] and n >= 4       # process+thread meta, span, counter
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    span = next(e for e in evs if e["ph"] == "X")
    assert span["name"] == "step"
    assert span["dur"] == pytest.approx(2000.0)    # seconds -> µs
    assert span["args"]["step_num"] == 3
    counter = next(e for e in evs if e["ph"] == "C")
    assert counter["name"] == "wps"
    assert counter["args"]["value"] == 1000.0
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)


def test_chrome_trace_invalid_files(tmp_path):
    bad = tmp_path / "trace.json"
    bad.write_text("{}")
    _, errs = tel.validate_chrome_trace(str(bad))
    assert errs
    bad.write_text(json.dumps(
        {"traceEvents": [{"ph": "X", "name": "s", "ts": 0}]}))
    _, errs = tel.validate_chrome_trace(str(bad))
    assert any("dur" in e for e in errs)


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------

def test_drift_monitor_ratios_on_synthetic_pairs():
    rec, mem, _ = make_recorder()
    mon = tel.DriftMonitor(
        {"step": 1.0, "compute": 0.6, "collective": 0.3, "bubble": 0.1},
        telemetry=rec)
    w = mon.observe({"step": 2.0, "compute": 0.6, "collective": 0.15,
                     "data": 0.01}, n_steps=10)
    r = w["predicted_over_measured"]
    assert r["step"] == pytest.approx(0.5)
    assert r["compute"] == pytest.approx(1.0)
    assert r["collective"] == pytest.approx(2.0)
    assert "data" not in r          # measured-only term: no prediction
    assert "bubble" not in r        # predicted-only term: no measurement
    gauges = {e["name"]: e["value"] for e in mem.by_kind("gauge")}
    assert gauges["drift/predicted_over_measured/step"] == \
        pytest.approx(0.5)
    assert gauges["drift/predicted_over_measured/collective"] == \
        pytest.approx(2.0)


def test_drift_monitor_zero_measured_gives_null_not_inf():
    mon = tel.DriftMonitor({"collective": 0.3})
    w = mon.observe({"collective": 0.0})
    assert w["predicted_over_measured"]["collective"] is None
    assert mon.summary()["mean_predicted_over_measured"] == {}


def test_drift_monitor_window_accumulation_and_artifact(tmp_path):
    mon = tel.DriftMonitor({"step": 1.0}, meta={"spec": "fsdp"})
    mon.observe({"step": 2.0}, n_steps=5)
    mon.observe({"step": 1.0}, n_steps=5)
    path = str(tmp_path / "drift.json")
    doc = mon.write(path)
    assert doc["n_windows"] == 2
    assert doc["mean_predicted_over_measured"]["step"] == \
        pytest.approx(0.75)
    on_disk = json.load(open(path))
    assert on_disk == doc
    assert on_disk["meta"]["spec"] == "fsdp"
    assert [w["window"] for w in on_disk["windows"]] == [0, 1]


def test_costmodel_decomposition_consistency():
    from repro.configs.llama2 import LLAMA2_7B
    from repro.core import costmodel as cm
    rep = cm.step_time(LLAMA2_7B, cm.H100, cm.Strategy(128, zero_stage=2),
                       256, 4096)
    d = rep.decomposition()
    assert d["step"] == rep.t_step
    assert d["compute"] == rep.t_compute
    assert d["collective"] == rep.t_comm_exposed
    assert d["bubble"] >= 0
    assert d["compute"] + d["collective"] + d["bubble"] == \
        pytest.approx(d["step"])
    # every nonzero comm kind appears namespaced
    for k, v in rep.comm_breakdown.items():
        assert (f"comm/{k}" in d) == bool(v)


# ---------------------------------------------------------------------------
# serve: per-request latency accounting vs injectable clock
# ---------------------------------------------------------------------------

def test_scheduler_lifecycle_latencies_fake_clock():
    from repro.serve.paged_cache import BlockAllocator
    from repro.serve.scheduler import Scheduler
    rec, mem, clk = make_recorder(FakeClock(10.0))
    sched = Scheduler(n_slots=1, allocator=BlockAllocator(64, 16),
                      clock=clk, telemetry=rec)
    r0 = sched.submit(np.arange(8), n_new=4)
    clk.advance(1.0)
    r1 = sched.submit(np.arange(8), n_new=4)
    clk.advance(2.0)
    sched.admit()                       # only r0 fits (1 slot)
    first = sched.running[0]            # request in slot 0
    assert first.rid == r0
    assert first.t_submit == 10.0 and first.t_admit == 13.0
    clk.advance(4.0)
    sched.complete(first)
    assert first.t_finish == 17.0
    sched.admit()                       # r1 admitted after r0 freed
    second = sched.running[0]
    assert second.rid == r1 and second.t_admit == 17.0

    snap = rec.metrics.snapshot()
    assert snap["serve/queue_wait_s"]["count"] == 2
    assert sorted(e["value"] for e in
                  mem.by_name("serve/queue_wait_s")) == [3.0, 6.0]
    assert snap["serve/total_latency_s"]["p50"] == 7.0
    assert snap["serve/submitted"]["value"] == 2
    assert snap["serve/admitted"]["value"] == 2
    assert snap["serve/completed"]["value"] == 1


def test_scheduler_expiry_and_cancel_counters():
    from repro.serve.paged_cache import BlockAllocator
    from repro.serve.scheduler import Scheduler
    rec, _, clk = make_recorder(FakeClock(10.0))
    sched = Scheduler(n_slots=2, allocator=BlockAllocator(64, 16),
                      clock=clk, telemetry=rec)
    sched.submit(np.arange(4), n_new=2, ttl_s=1.0)
    rid2 = sched.submit(np.arange(4), n_new=2)
    sched.admit()
    clk.advance(2.0)
    assert len(sched.expire()) == 1
    sched.cancel(rid2)
    snap = rec.metrics.snapshot()
    assert snap["serve/expired"]["value"] == 1
    assert snap["serve/cancelled"]["value"] == 1
    assert "serve/completed" not in snap


def test_engine_telemetry_accounting():
    jax = pytest.importorskip("jax")
    from repro.configs import get_config, reduced
    from repro.models import Runtime, init_params
    from repro.serve.engine import ServeEngine

    cfg = reduced(get_config("qwen3-0.6b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    # recorder stamps use its own (fake) clock; the engine keeps the
    # real monotonic clock for lifecycle timestamps
    rec, mem, _ = make_recorder()
    eng = ServeEngine(cfg, params, Runtime(), max_len=64, n_slots=2,
                      telemetry=rec)
    assert eng.paged_ok
    prompts = np.ones((2, 8), np.int32)
    out = eng.generate(prompts, n_new=6, key=jax.random.PRNGKey(1))
    assert out.shape == (2, 14)
    snap = rec.metrics.snapshot()
    assert snap["serve/submitted"]["value"] == 2
    assert snap["serve/completed"]["value"] == 2
    # 2 requests x 6 tokens, each with a latency sample: the 2 first
    # tokens come out of prefill (TTFT), the remaining 10 from decode
    # segments (weighted per-token observations)
    ttft = snap["serve/ttft_s"]
    tok = snap["serve/token_latency_s"]
    assert ttft["count"] == 2
    assert ttft["count"] + tok["count"] == 12
    assert snap["serve/batch_occupancy"]["value"] is not None
    assert 0.0 <= snap["serve/block_util"]["value"] <= 1.0
    assert mem.by_name("serve/tick")
    assert mem.by_name("serve/prefill_chunk")
    assert mem.by_name("serve/decode_segment")


# ---------------------------------------------------------------------------
# trainer + supervisor integration
# ---------------------------------------------------------------------------

def _tiny_train(telemetry, drift=None, steps=4, fault_plan=None):
    import jax
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeConfig
    from repro import strategy as strategy_lib
    from repro.core import parallel as par
    from repro.data.pipeline import Batcher, SyntheticSource
    from repro.train.trainer import TrainConfig, train_loop

    cfg = reduced(get_config("qwen3-0.6b"), n_layers=2, d_model=64)
    shape = ShapeConfig("tel", 16, 4, "train")
    strat = strategy_lib.parse("ddp")
    topo = strategy_lib.host_topology()
    plan = strat.to_plan(cfg, topo, shape)
    rt = par.make_runtime(cfg, plan, shape)
    tc = TrainConfig(steps=steps, warmup=1, log_every=2)
    return train_loop(cfg, plan, rt, tc,
                      Batcher(SyntheticSource(cfg.vocab_size, seed=7),
                              16, 4),
                      key=jax.random.PRNGKey(0), fault_plan=fault_plan,
                      telemetry=telemetry, drift=drift)


def test_train_step_scopes_reach_the_compiled_program():
    """Each named scope of the train step is in the compiled program's
    ``op_name`` metadata: the model's both in the forward pass
    (``/jvp(<scope>)/``) and in the backward (``transpose(jvp(<scope>))``),
    the optimizer's as they are.  A profile is split by these names."""
    import re

    import jax
    import jax.numpy as jnp
    from repro import strategy as strategy_lib
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeConfig
    from repro.core import parallel as par
    from repro.models import transformer as tfm
    from repro.optim import adamw, init_opt_state
    from repro.train.trainer import TrainConfig, make_train_step

    cfg = reduced(get_config("qwen3-0.6b"), n_layers=2, d_model=64)
    shape = ShapeConfig("scopes", 16, 4, "train")
    plan = strategy_lib.parse("ddp").to_plan(
        cfg, strategy_lib.host_topology(), shape)
    step = make_train_step(cfg, par.make_runtime(cfg, plan, shape),
                           TrainConfig(steps=4, warmup=1))
    pshapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                             jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    with par.use_mesh(plan.mesh):
        text = jax.jit(step).lower(
            pshapes, jax.eval_shape(init_opt_state, pshapes),
            {"tokens": tok, "labels": tok}).compile().as_text()
    for s in tfm.MODEL_SCOPES:
        assert re.search(rf"/jvp\({s}\)/", text), s
        assert f"/transpose(jvp({s}))/" in text, s
    for s in adamw.OPTIMIZER_SCOPES:
        assert f"/{s}/" in text, s


def test_trainer_spans_gauges_and_drift_windows():
    pytest.importorskip("jax")
    rec, mem, _ = make_recorder(time.monotonic)
    drift = tel.DriftMonitor({"step": 1e-3, "compute": 5e-4},
                             telemetry=rec)
    _tiny_train(rec, drift=drift, steps=4)
    steps = mem.by_name("train/step")
    assert len(steps) == 4
    assert [s["attrs"]["step_num"] for s in steps] == [0, 1, 2, 3]
    assert len(mem.by_name("train/dispatch")) == 4
    # dispatch and wait are separate spans, and the host sync happens
    # only on logging windows (steps 0 [first], 1, 3 with log_every=2)
    # — the async-dispatch satellite
    assert len(mem.by_name("train/wait")) == 3
    snap = rec.metrics.snapshot()
    assert snap["train/wps"]["value"] > 0
    assert 0.0 <= snap["train/goodput_frac"]["value"] <= 1.0
    # one measured drift window per logging window, with a real ratio
    assert len(drift.windows) == 3
    for w in drift.windows:
        assert w["measured"]["step"] > 0
        assert w["predicted_over_measured"]["step"] is not None


def test_trainer_per_step_sync_gated_on_stragglers():
    pytest.importorskip("jax")
    from repro.resilience.faults import FaultEvent, FaultPlan
    # a fault plan without stragglers keeps dispatch async (log-window
    # syncs only); a straggler plan needs the measured step time, so it
    # syncs every step
    no_straggler = FaultPlan(
        events=[FaultEvent(step=10 ** 6, kind="ckpt_io")])
    straggler = FaultPlan(
        events=[FaultEvent(step=10 ** 6, kind="straggler",
                           magnitude=1.5)])
    for plan, n_waits_expected in ((no_straggler, 3), (straggler, 4)):
        rec, mem, _ = make_recorder(time.monotonic)
        _tiny_train(rec, steps=4, fault_plan=plan)
        assert len(mem.by_name("train/wait")) == n_waits_expected


def test_supervisor_event_log_jsonl_sibling(tmp_path):
    from repro.resilience.supervisor import Supervisor, SupervisorConfig
    log = str(tmp_path / "events.json")
    rec, mem, _ = make_recorder()
    sup = Supervisor(SupervisorConfig(max_restarts=1, backoff_base_s=0.0,
                                      event_log_path=log), telemetry=rec)
    calls = {"n": 0}

    def attempt(n, strategy, topology):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        return "ok"

    assert sup.run(attempt) == "ok"
    # pinned summary format intact
    doc = json.load(open(log))
    assert doc["n_failures"] == 1
    assert [e["kind"] for e in doc["events"]] == ["failure", "completed"]
    assert "backoff_s" in doc["events"][0]       # post-record mutation
    # telemetry-schema sibling, written by the shared sink, validates
    sib = str(tmp_path / "events.jsonl")
    n, errs = tel.validate_jsonl(sib)
    assert errs == [] and n == 2
    lines = [json.loads(l) for l in open(sib)]
    assert lines[0]["name"] == "supervisor/failure"
    assert lines[0]["attrs"]["backoff_s"] == 0.0
    assert lines[1]["name"] == "supervisor/completed"
    # recorder counters observed the lifecycle
    snap = rec.metrics.snapshot()
    assert snap["supervisor/failure"]["value"] == 1
    assert snap["supervisor/completed"]["value"] == 1
    assert mem.by_name("supervisor/attempt")


# ---------------------------------------------------------------------------
# train-CLI smoke: well-formed trace artifact (acceptance criterion)
# ---------------------------------------------------------------------------

def test_train_cli_trace_smoke(tmp_path):
    pytest.importorskip("jax")
    trace = str(tmp_path / "trace.json")
    jsonl = str(tmp_path / "events.jsonl")
    drift = str(tmp_path / "drift.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--reduced",
         "--steps", "6", "--log_every", "2", "--seq_len", "32",
         "--global_batch", "4", "--host_devices", "2",
         "--strategy", "fsdp", "--trace", trace,
         "--metrics_jsonl", jsonl, "--drift_report", drift],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]

    # every emitted JSONL event validates against the schema
    n, errs = tel.validate_jsonl(jsonl)
    assert errs == [] and n > 0

    # the trace is loadable Chrome-trace JSON...
    n, errs = tel.validate_chrome_trace(trace)
    assert errs == [] and n > 0
    evs = json.load(open(trace))["traceEvents"]
    steps = [e for e in evs if e["ph"] == "X" and e["name"] == "train/step"]
    assert len(steps) == 6
    # ...whose per-step spans sum to within 10% of the wall-clock the
    # loop spent (first span start -> last span end), and never overlap
    total_span = sum(e["dur"] for e in steps)
    wall = max(e["ts"] + e["dur"] for e in steps) - \
        min(e["ts"] for e in steps)
    assert total_span >= 0.9 * wall
    assert total_span <= 1.01 * wall

    # drift artifact has per-term ratios including the step term
    doc = json.load(open(drift))
    assert doc["n_windows"] >= 1
    assert doc["predicted"]["compute"] > 0
    assert doc["predicted"]["collective"] >= 0
    ratios = doc["windows"][0]["predicted_over_measured"]
    assert ratios.get("step") is not None
