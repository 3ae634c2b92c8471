"""The DeepSeek-V2-Lite cell on a CPU at a reduced size: the whole program
(latent attention, the 8 held experts of 64 through the dropless grouped
product, the untied head) meets ``reference_mla_moe`` in float32 over the
checked steps; the job's record carries the held experts' item count and
its cost; each fault planted in the timed step fails the cell's limits;
and the step's ``moe_dispatch`` and ``experts`` scopes sit inside the
forward and backward parts."""
import time
import types

import jax
import pytest

from chip import bench, moe_trace, scopes
from chip import reference_base as base
from chip.conftest import load
from chip.jobs import train_moe

CELL = "deepseek-v2-lite.train-s8192"


@pytest.fixture
def tiny_mla_moe():
    """deepseek-v2-lite's file and train-s8192 at a CPU test's size: every
    width cut, the 64-expert router, top-6 and 8 held experts kept."""
    cfg = load("configs", "deepseek-v2-lite.json")
    cfg.update(hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
               v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
               num_hidden_layers=3, vocab_size=256)
    t = load("traffic", "train-s8192.json")
    t.update(name="tiny", seq_len=32, global_batch=1)
    t["runtime"] = dict(t["runtime"], attn_impl="jnp", norm_impl="jnp")
    return cfg, t


def test_reference_matches_the_program_in_float32(tiny_mla_moe):
    config, traffic = tiny_mla_moe
    job = train_moe.TrainJob(config, dict(traffic, strategy="fsdp_f32"), 1)
    assert job.cfg.moe.n_held == 8 and job.cfg.moe.n_experts == 64
    seed = 2**31 + 17
    *_, got, corpus = job.check_steps(seed)
    gaps = base.compare(got, job.reference(seed, corpus))
    assert gaps["loss_gap"][0] < 2e-5, gaps
    assert gaps["grad_norm_gap"][0] < 1e-4, gaps
    assert gaps["update_norm_gap"][0] < 1e-4, gaps
    assert gaps["left_out"] == []
    # one count a checked step, of items routed to the 8 held experts
    items = [float(x) for x in job.expert_items]
    assert len(items) == traffic["check_steps"]
    assert all(0 < n <= 2 * 32 * 6 for n in items)


def _run(config, traffic, fault, seconds=0.3):
    ctx = types.SimpleNamespace(
        config=config, traffic=traffic, chips=1, seed=2**31 + 211,
        seconds=seconds, trace=False, devices=jax.devices()[:1],
        t_start=time.perf_counter(), fault=fault)
    rec = train_moe.run(ctx)
    assert rec["steps"] >= 1 and rec["compiles_in_window"] == 0
    return rec


def test_record_carries_the_expert_items_and_their_cost(tiny_mla_moe):
    rec = _run(*tiny_mla_moe, None)
    items = rec["expert_items_per_step"]
    assert 0 < items <= 2 * 32 * 6
    cost = rec["expert_matmul_cost"]
    assert cost["flops"] == 18 * items * 64 * 32
    assert cost["bytes"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "unshifted_labels"])
def test_faults_fail_the_cell_limits(fault, tiny_mla_moe):
    rec = _run(*tiny_mla_moe, fault)
    correct, checks = bench.verdict(rec, load("limits", CELL + ".json"))
    assert not correct, checks


def test_dispatch_and_expert_scopes_sit_in_forward_and_backward(
        tiny_mla_moe):
    """Each new scope's instructions fall in the step's forward and
    backward parts (but for a few constants and reducer bodies outside
    the layer scan), so ``scopes.py``'s split reads as before."""
    config, traffic = tiny_mla_moe
    text = scopes.compiled_text(config, traffic, 1)
    parts = scopes.instruction_parts(text)
    for scope in ("moe_dispatch", "experts", "mla"):
        got = [parts[n] for n in moe_trace.scope_instructions(text, scope)]
        assert {"forward", "backward"} <= set(got), scope
        inside = sum(p in ("forward", "backward") for p in got)
        assert inside >= 0.8 * len(got), (scope, len(got), inside)
    assert not moe_trace.scope_instructions(text, "no_such_scope")


def test_grouped_matmul_readers_on_a_small_trace():
    """``expert_matmul_ms`` sums the gmm and tgmm kernels per step, and
    nothing that only names them; the roofline share reads the record's
    cost."""
    tr = {"window_s": 1.0, "devices": {0: {"busy_s": 1.0, "ops": {
        "gmm.48": 0.004, "tgmm.15": 0.006, "fusion.9": 0.2,
        "copy_gmm_fusion.2": 0.5}}}}
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    run = {"trace": tr, "chips": 1, "peak": peak, "record": {
        "traced_steps": 2,
        "expert_matmul_cost": {"flops": 2.5e9, "bytes": 1e6}}}
    ms = bench._load_module(
        f"{bench.HERE}/metrics/expert_matmul_ms.py", "m_ms").read(run)
    assert abs(ms - 5.0) < 1e-9
    share = bench._load_module(
        f"{bench.HERE}/metrics/expert_matmul_roofline.py",
        "m_rl").read(run)
    assert abs(share - 50.0) < 1e-9
    run["record"].pop("expert_matmul_cost")
    assert bench._load_module(
        f"{bench.HERE}/metrics/expert_matmul_roofline.py",
        "m_rl").read(run) is None
