"""The train step split into its parts, from the program's named scopes.

The program wraps the parts of its step in ``jax.named_scope``s, which
land in the ``op_name`` of every compiled instruction they cover
(``jit(train_step)/jvp(blocks)/while/body/...``; the backward pass reads
``transpose(jvp(blocks))``).  The trace names each device op by its
instruction name alone (``trace.load``), so the map from instruction to
part comes from the compiled step's own text: the cell's step compiled
again from abstract, sharded shapes, a hit in the persistent compilation
cache that allocates nothing on the device.

The scope names are the benchmark's own copy: a scope the program renames
or drops shows up as ``other``, and is not followed in silence.
"""
from __future__ import annotations

import re
import time
from collections import Counter, defaultdict
from typing import Dict, Optional

from chip import trace

FORWARD_SCOPES = ("embed", "blocks", "final_norm")
HEAD_LOSS_SCOPES = ("lm_head", "xent_loss")
OPTIMIZER_SCOPES = ("grad_clip", "adamw")
PARTS = ("forward", "backward", "lm_head_loss", "optimizer", "other")
TOP = 10
COLLECTIVE_OPS = {"all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute", "collective-broadcast", "send",
                  "recv"}
MATMUL_OPS = {"convolution", "dot"}

# the transformations JAX writes round a scope: `transpose(jvp(blocks))`
_WRAPPED = re.compile(r"^(jvp|transpose)\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \S+ ([\w\-]+)\(")
_TUPLE_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \(.*?\) "
                          r"([\w\-]+)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def part_of(op_name: str) -> str:
    """The part of the step an instruction with this ``op_name`` belongs
    to; a path segment names a scope only when it equals it, once the
    transformations round it are peeled off."""
    scopes, backward = set(), False
    for seg in op_name.split("/"):
        m = _WRAPPED.match(seg)
        while m:
            backward = backward or m.group(1) == "transpose"
            seg = m.group(2)
            m = _WRAPPED.match(seg)
        if seg in FORWARD_SCOPES:
            scopes.add("backward" if backward else "forward")
        elif seg in HEAD_LOSS_SCOPES:
            scopes.add("lm_head_loss")
        elif seg in OPTIMIZER_SCOPES:
            scopes.add("optimizer")
    for part in ("optimizer", "lm_head_loss", "backward", "forward"):
        if part in scopes:
            return part
    return "other"


def instructions(hlo_text: str) -> Dict[str, dict]:
    """-> {instruction name: {"opcode", "op_name", "computation" (the one
    it is in), "calls" (the computations it calls), "refs" (every name
    its line refers to)}} for every instruction of a compiled text."""
    out: Dict[str, dict] = {}
    comp = ""
    for line in hlo_text.splitlines():
        m = _INSTR.match(line) or _TUPLE_INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
            continue
        op = _OP_NAME.search(line)
        out[m.group(1)] = {"opcode": m.group(2),
                           "op_name": op.group(1) if op else "",
                           "computation": comp,
                           "calls": _CALLS.findall(line),
                           "refs": _REF.findall(line.split(" = ", 1)[1])}
    return out


def instruction_parts(hlo_text: str) -> Dict[str, str]:
    """-> {instruction name: part}.  An instruction the scopes leave out
    (no ``op_name`` of the program's, as a compiler-made copy, buffer or
    fusion may have) takes, in this order, the part most instructions of
    the computations it calls have (a fusion's body), the part most of
    its users have (a buffer made for the op that reads it), or the part
    of the instruction that calls the computation it is in (the body of
    a loop)."""
    instrs = instructions(hlo_text)
    parts = {n: part_of(i["op_name"]) for n, i in instrs.items()}
    users = defaultdict(list)
    callers = {}
    for n, i in instrs.items():
        for r in i["refs"]:
            if r in instrs and r != n:
                users[r].append(n)
        for c in i["calls"]:
            callers.setdefault(c, n)
    # no op_name, or one the partitioner made up (`broadcast.78`), which
    # is no path of the program's; a parameter keeps its argument's name
    unset = [n for n, i in instrs.items()
             if "/" not in i["op_name"] and i["opcode"] != "parameter"]

    def most(names):
        c = Counter(parts[x] for x in names if parts[x] != "other")
        return c.most_common(1)[0][0] if c else None

    by_comp = defaultdict(list)
    for n, i in instrs.items():
        by_comp[i["computation"]].append(n)
    for _ in range(8):        # each pass resolves one more level
        changed = False
        for n in unset:
            i = instrs[n]
            got = most([x for c in i["calls"] for x in by_comp.get(c, ())]) \
                or most(users.get(n, ()))
            if got is None and i["computation"] in callers:
                got = parts[callers[i["computation"]]]
            if got and got != "other" and got != parts[n]:
                parts[n], changed = got, True
        if not changed:
            break
    return parts


def collective_kinds(hlo_text: str) -> Dict[str, str]:
    """-> {instruction name: collective kind} for each collective op of a
    compiled text: a collective instruction (``all-gather``,
    ``all-gather-start``, ...), and a fusion that holds a collective and no
    matrix product (the TPU compiler's asynchronous start or done of a
    collective, named ``async-collective-start``; a fusion that also
    multiplies runs a slice of the collective under its compute, and is
    compute); an all-reduce fused with the slice a chip keeps is a
    reduce-scatter."""
    instrs = instructions(hlo_text)

    def kind(opcode):
        base = re.sub(r"-(start|done)$", "", opcode)
        return base if base in COLLECTIVE_OPS else None

    by_comp = defaultdict(list)
    for i in instrs.values():
        by_comp[i["computation"]].append(i["opcode"])
    out = {}
    for n, i in instrs.items():
        k = kind(i["opcode"])
        if k is None and i["opcode"] == "fusion":
            ops = [op for c in i["calls"] for op in by_comp.get(c, ())]
            kinds = [kind(op) for op in ops if kind(op)]
            if kinds and not MATMUL_OPS & set(ops):
                k = kinds[0]
                if k == "all-reduce" and "dynamic-slice" in ops:
                    k = "reduce-scatter"    # each chip keeps its slice
        if k:
            out[n] = k
    return out


def split(reduced: dict, parts: Dict[str, str], kinds: Dict[str, str],
          steps: int) -> dict:
    """Each chip's leaf-op time a step summed into the parts, averaged
    over the chips -> {"ms": {part: ms/step}, "attributed": share of the
    leaf-op time in a part other than ``other``, "other_ops": the largest
    ``other`` ops [[name, ms/step]], "collectives": {(kind, part):
    ms/step}, "unnamed_collective_s": {chip: seconds of collective ops
    whose names ``trace.COLLECTIVE_RE`` misses, which ``trace.reduce``
    counts as compute}}."""
    n = len(reduced["devices"])
    ms = dict.fromkeys(PARTS, 0.0)
    others: Dict[str, float] = defaultdict(float)
    coll: Dict[tuple, float] = defaultdict(float)
    unnamed: Dict[int, float] = {}
    for dev, d in reduced["devices"].items():
        unnamed[dev] = 0.0
        for name, s in d["ops"].items():
            if trace.CONTAINER_RE.match(name):
                continue
            part = parts.get(name, "other")
            v = 1e3 * s / steps / n
            ms[part] += v
            if part == "other":
                others[name] += v
            if name in kinds:
                coll[kinds[name], part] += v
                if not trace.COLLECTIVE_RE.search(name):
                    unnamed[dev] += s
    total = sum(ms.values())
    return {"ms": ms,
            "attributed": 1.0 - ms["other"] / total if total else 0.0,
            "other_ops": sorted(([k, v] for k, v in others.items()),
                                key=lambda x: -x[1])[:TOP],
            "collectives": dict(coll), "unnamed_collective_s": unnamed}


def compiled_text(config: dict, traffic: dict, chips: int) -> str:
    """The cell's train step, compiled from abstract shapes in the
    shardings the run used -> its compiled text."""
    import jax
    import jax.numpy as jnp
    from chip.jobs.train import TrainJob
    from repro.models import transformer as tfm
    from repro.optim import init_opt_state
    from repro.train.trainer import jit_train_step

    job = TrainJob(config, traffic, chips)
    pshapes = jax.eval_shape(lambda k: tfm.init_params(job.cfg, k),
                             jax.random.PRNGKey(0))
    oshapes = jax.eval_shape(init_opt_state, pshapes)
    tok = jax.ShapeDtypeStruct((job.batch, job.seq), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    with job.par.use_mesh(job.plan.mesh):
        bshard = job.par.batch_specs(job.cfg, job.plan, batch)
        step = jit_train_step(job.step_fn, job.pshard, job.oshard, bshard)
        return step.lower(pshapes, oshapes, batch).compile().as_text()


_SPLITS: Dict[str, dict] = {}


def step_split(run: dict) -> Optional[dict]:
    """``split`` of a traced run, memoised per workload (several readers
    ask for it); logs the split, the largest unattributed ops and, on
    several chips, collective time by kind and part.  None without a
    trace."""
    tr, rec = run["trace"], run["record"]
    if not tr or not rec.get("traced_steps"):
        return None
    if run["workload"] not in _SPLITS:
        t0 = time.perf_counter()
        text = compiled_text(run["config"], run["traffic"], run["chips"])
        s = split(tr, instruction_parts(text), collective_kinds(text),
                  rec["traced_steps"])
        print(f"[split] step text compiled and mapped in "
              f"{time.perf_counter() - t0:.2f} s", flush=True)
        print("[split] " + " ".join(f"{p} {s['ms'][p]:.3f}" for p in PARTS)
              + f" ms/step (attributed {100 * s['attributed']:.2f}%)",
              flush=True)
        print("[split] largest other ops (ms/step): " + ", ".join(
            f"{n} {v:.3f}" for n, v in s["other_ops"]), flush=True)
        if len(tr["devices"]) > 1:
            print("[split] collectives (ms/step): " + ", ".join(
                f"{k} in {p} {v:.3f}" for (k, p), v in
                sorted(s["collectives"].items(), key=lambda x: -x[1])),
                flush=True)
        _SPLITS[run["workload"]] = s
    return _SPLITS[run["workload"]]


def part_ms(run: dict, part: str) -> Optional[float]:
    """A part's ms a step; None without a trace or where no op fell in it
    (a program without the scopes)."""
    s = step_split(run)
    if s is None or not s["ms"][part]:
        return None
    return s["ms"][part]
