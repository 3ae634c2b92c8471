"""The step split: ops sorted into parts by the program's named scopes,
on hand-written compiled text, a hand-made trace, and the cells' own steps
compiled on the CPU at a test's size."""
import importlib
import os

import pytest

from chip import scopes
from chip.conftest import HERE

FWD = "jit(train_step)/jvp(blocks)/while/body/closed_call"
BWD = "jit(train_step)/transpose(jvp(blocks))/while/body/closed_call"


@pytest.mark.parametrize("op_name,part", [
    (FWD + "/bsd,df->bsf/dot_general", "forward"),
    (FWD + "/jit(flash_attention)/flash_attention_fwd/pallas_call",
     "forward"),
    (BWD + "/bsd,df->bsf/dot_general", "backward"),
    # the backward rule of a custom_vjp (the flash-attention kernels)
    (BWD + "/jit(flash_attention)/flash_attention_bwd_dq/pallas_call",
     "backward"),
    ("jit(train_step)/jvp(embed)/jit(_take)/gather", "forward"),
    ("jit(train_step)/transpose(jvp(embed))/jit(_take)/scatter-add",
     "backward"),
    ("jit(train_step)/transpose(jvp(final_norm))/jit(rmsnorm)/rmsnorm_bwd"
     "/pallas_call", "backward"),
    # a transposition above the scope still makes it backward
    ("jit(train_step)/transpose(jvp(model))/blocks/while/body/add",
     "backward"),
    ("jit(train_step)/jvp(lm_head)/bsd,dv->bsv/dot_general", "lm_head_loss"),
    ("jit(train_step)/transpose(jvp(lm_head))/bsd,dv->bsv/dot_general",
     "lm_head_loss"),
    ("jit(train_step)/transpose(jvp(xent_loss))/jit(take_along_axis)/"
     "scatter-add", "lm_head_loss"),
    ("jit(train_step)/grad_clip/reduce_sum", "optimizer"),
    ("jit(train_step)/adamw/sub", "optimizer"),
    # a collective GSPMD put in takes the op it serves
    (BWD + "/bsf,fd->bsd/dot_general", "backward"),
    ("jit(train_step)/pow", "other"),
    ("jit(train_step)/jvp()/iota", "other"),
    ("", "other"),
    # a segment names a scope only when it equals it
    ("jit(xent_loss_fn)/reduce_max", "other"),
    ("jit(train_step)/jit(blocks)/mul", "other"),
    ("jit(train_step)/adamw_like/sub", "other"),
])
def test_part_of(op_name, part):
    assert scopes.part_of(op_name) == part


HLO = r'''HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[8,16]) -> bf16[8,16] {
  %param_0 = bf16[8,16]{1,0} parameter(0)
  ROOT %multiply.1 = bf16[8,16]{1,0} multiply(%param_0, %param_0), metadata={op_name="jit(train_step)/transpose(jvp(blocks))/while/body/mul"}
}

%gather_computation.2 (param_0.1: bf16[2,16]) -> bf16[8,16] {
  %param_0.1 = bf16[2,16]{1,0} parameter(0)
  %all-gather.3 = bf16[8,16]{1,0} all-gather(%param_0.1), dimensions={0}, metadata={op_name="jit(train_step)/jvp(blocks)/while/body/dot_general"}
  ROOT %custom-call.4 = bf16[8,16]{1,0} custom-call(%all-gather.3), custom_call_target="AsyncCollectiveDone"
}

%all-reduce-scatter.6 (input: bf16[8,16]) -> bf16[2,16] {
  %input = bf16[8,16]{1,0} parameter(0)
  %all-reduce.7 = bf16[8,16]{1,0} all-reduce(%input), to_apply=%add
  %partition-id.1 = u32[] partition-id()
  ROOT %dynamic-slice.1 = bf16[2,16]{1,0} dynamic-slice(%all-reduce.7, %partition-id.1, %c), dynamic_slice_sizes={2,16}
}

%body.5 (p: (s32[], bf16[8,16])) -> (s32[], bf16[8,16]) {
  %p = (s32[], bf16[8,16]{1,0}) parameter(0)
  %gte.1 = bf16[8,16]{1,0} get-tuple-element(%p), index=1
  %fusion.1 = bf16[8,16]{1,0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  %flash_attention_bwd_dq.3 = bf16[8,16]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(blocks))/while/body/closed_call/jit(flash_attention)/flash_attention_bwd_dq/pallas_call"}
  %copy.7 = bf16[8,16]{1,0} copy(%flash_attention_bwd_dq.3)
  ROOT %tuple.2 = (s32[], bf16[8,16]{1,0}) tuple(%gte.0, %copy.7)
}

ENTRY %main.9 (a: bf16[2,16]) -> bf16[8,16] {
  %a = bf16[2,16]{1,0} parameter(0), metadata={op_name="params[\'embed\'][\'tok\']"}
  %all-reduce.6 = f32[16]{0} all-reduce(%a), to_apply=%add, metadata={op_name="jit(train_step)/grad_clip/reduce_sum"}
  %async-collective-done = bf16[8,16]{1,0} fusion(%a), kind=kCustom, calls=%gather_computation.2, metadata={op_name="jit(train_step)/jvp(blocks)/while/body/dot_general"}
  %fusion.8 = bf16[2,16]{1,0} fusion(%a), kind=kCustom, calls=%all-reduce-scatter.6, metadata={op_name="jit(train_step)/transpose(jvp(blocks))/while/body/dot_general"}
  %reduce_max.2 = f32[8]{0} reduce(%a), to_apply=%max, metadata={op_name="jit(xent_loss_fn)/reduce_max"}
  %broadcast.5 = bf16[8,16]{1,0} broadcast(%constant.1), dimensions={}, metadata={op_name="broadcast.2"}
  %tuple.1 = (s32[], bf16[8,16]{1,0}) tuple(%constant.2, %broadcast.5)
  %while.3 = (s32[], bf16[8,16]{1,0}) while(%tuple.1), condition=%cond.4, body=%body.5, metadata={op_name="jit(train_step)/transpose(jvp(blocks))/while"}
  ROOT %pow.1 = f32[] power(%c, %d), metadata={op_name="jit(train_step)/pow"}
}
'''


def test_instruction_parts_on_compiled_text():
    parts = scopes.instruction_parts(HLO)
    assert parts["flash_attention_bwd_dq.3"] == "backward"
    assert parts["all-reduce.6"] == "optimizer"
    assert parts["async-collective-done"] == "forward"
    assert parts["reduce_max.2"] == "other"     # jit(xent_loss_fn)
    assert parts["pow.1"] == "other"            # no scope
    assert parts["a"] == "other"                # a parameter
    # no op_name of the program's: a fusion takes its body's part, a
    # buffer its users', an op inside a loop body its loop's
    assert parts["fusion.1"] == "backward"
    assert parts["fusion.8"] == "backward"
    assert parts["broadcast.5"] == "backward"
    assert parts["copy.7"] == "backward"


def test_collective_kinds_on_compiled_text():
    kinds = scopes.collective_kinds(HLO)
    assert kinds["all-reduce.6"] == "all-reduce"
    # an asynchronous collective the compiler wrapped in a fusion
    assert kinds["async-collective-done"] == "all-gather"
    # an all-reduce fused with the slice each chip keeps
    assert kinds["fusion.8"] == "reduce-scatter"
    assert "fusion.1" not in kinds and "while.3" not in kinds


def test_split_of_a_hand_made_trace():
    reduced = {"window_s": 0.1, "devices": {
        0: {"ops": {"fusion.1": 0.010, "flash_attention_bwd_dq.3": 0.004,
                    "while.3": 0.050, "pow.1": 0.002, "all-reduce.6": 0.002,
                    "async-collective-done": 0.001, "unknown.9": 0.001},
            "collective_exposed_s": 0.002},
        1: {"ops": {"fusion.1": 0.012, "flash_attention_bwd_dq.3": 0.004,
                    "while.3": 0.050, "all-reduce.6": 0.004,
                    "async-collective-done": 0.003},
            "collective_exposed_s": 0.004}}}
    parts = scopes.instruction_parts(HLO)
    s = scopes.split(reduced, parts, scopes.collective_kinds(HLO), steps=2)
    ms = s["ms"]
    # per step (2) and averaged over the chips (2); `while` left out
    assert ms["backward"] == pytest.approx((0.022 + 0.008) * 1e3 / 4)
    assert ms["optimizer"] == pytest.approx(0.006 * 1e3 / 4)
    assert ms["forward"] == pytest.approx(0.004 * 1e3 / 4)
    assert ms["other"] == pytest.approx(0.003 * 1e3 / 4)
    assert ms["lm_head_loss"] == 0
    total = sum(ms.values())
    assert s["attributed"] == pytest.approx(1 - ms["other"] / total)
    assert s["other_ops"][0] == ["pow.1", pytest.approx(0.5)]
    assert s["collectives"] == {
        ("all-reduce", "optimizer"): pytest.approx(1.5),
        ("all-gather", "forward"): pytest.approx(1.0)}
    # the collective trace.COLLECTIVE_RE does not see by name
    assert s["unnamed_collective_s"] == {0: pytest.approx(0.001),
                                         1: pytest.approx(0.003)}


def _compiled_parts(config, traffic, chips):
    text = scopes.compiled_text(config, traffic, chips)
    return text, scopes.instructions(text), scopes.instruction_parts(text)


def test_tiny_step_is_split_into_every_part(tiny_config, tiny_traffic):
    """The step the benchmark runs, compiled on the CPU: every part has
    ops, and the scopes place nearly every fusion, product and kernel."""
    from chip.jobs.train import TrainJob
    text, instrs, parts = _compiled_parts(tiny_config, tiny_traffic, 1)
    for part in scopes.PARTS:
        assert part in parts.values(), part
    work = [n for n, i in instrs.items()
            if i["opcode"] in ("fusion", "dot", "convolution", "custom-call")]
    placed = [n for n in work if parts[n] != "other"]
    assert len(placed) >= 0.95 * len(work), (len(placed), len(work))
    # the instructions are those of the step the run compiles from its
    # own state
    job = TrainJob(tiny_config, tiny_traffic, 1)
    job.check_steps(2**31 + 11)
    ran = scopes.instructions(job.compiled.as_text())
    assert [(n, i["opcode"], i["op_name"]) for n, i in ran.items()] == \
        [(n, i["opcode"], i["op_name"]) for n, i in instrs.items()]


def test_every_collective_of_the_fsdp4_step_has_a_part(tiny_fsdp4):
    config, traffic = tiny_fsdp4
    text, instrs, parts = _compiled_parts(config, traffic, 4)
    kinds = scopes.collective_kinds(text)
    assert kinds
    assert {k: parts[k] for k in kinds if parts[k] == "other"} == {}


NEW_READERS = ("forward_ms", "backward_ms", "lm_head_loss_ms",
               "optimizer_ms", "collective_exposed_pct")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "test_metric_" + name, os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_return_none_without_a_trace(name):
    run = {"trace": None, "record": {}, "workload": "none", "chips": 1}
    assert _reader(name).read(run) is None


def test_part_readers_are_silent_for_a_program_without_scopes(monkeypatch):
    """Every op in ``other``, as in a program without the scopes: the part
    readers give nothing; the exposed share is still read."""
    split = {"ms": dict.fromkeys(scopes.PARTS, 0.0), "attributed": 0.0,
             "other_ops": [], "collectives": {},
             "unnamed_collective_s": {0: 0.0}}
    split["ms"]["other"] = 80.0
    monkeypatch.setitem(scopes._SPLITS, "cell", split)
    run = {"trace": {"window_s": 2.0, "devices": {
        0: {"collective_exposed_s": 0.1}}},
        "record": {"traced_steps": 20}, "workload": "cell", "chips": 1}
    for name in NEW_READERS[:4]:
        assert _reader(name).read(run) is None, name
    assert _reader("collective_exposed_pct").read(run) == pytest.approx(5.0)
