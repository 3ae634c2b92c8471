"""Parse collective-communication volume out of compiled HLO text.

``cost_analysis()`` does not report collective bytes, so the roofline's
collective term is derived here.  Because lax.scan lowers to HLO while
loops whose bodies appear once in the text, a naive line scan undercounts
by the trip count; ``collective_stats`` therefore walks the computation
graph and multiplies while-body contributions by the
``known_trip_count`` annotation XLA attaches to each loop.

Byte convention: for every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op we sum the byte size of the *result*
shapes (async ``-start`` counted once, ``-done`` ignored).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# tuple-typed params nest parentheses, so match greedily up to '->'
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{$")
_COLL_RE = re.compile(
    r"=\s*(.+?)\s+(" + "|".join(COLLECTIVES) + r")(-start)?\(")
_WHILE_RE = re.compile(r"\bwhile\(")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count...?.?.n.:.?"?(\d+)')
_CALL_RE = re.compile(r"\b(?:call|async-start)\(.*?to_apply=%?([\w.\-]+)")
_COND_RE = re.compile(
    r"(?:true_computation=%?([\w.\-]+).*?false_computation=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\})")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _split_computations(hlo_text: str) -> Dict[str, list]:
    comps: Dict[str, list] = {}
    cur = None
    entry = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        m = _COMP_HDR_RE.match(line) if (line.endswith("{")
                                         and not raw.startswith(" ")) else None
        if m:
            cur = m.group(1)
            comps[cur] = []
            if raw.startswith("ENTRY"):
                entry = cur
            continue
        if line == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    if entry is None and comps:
        entry = next(iter(comps))
    comps["__entry__"] = [entry]  # type: ignore[list-item]
    return comps


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """-> {op_kind: {'bytes': loop-scaled result bytes, 'count': n_ops}}."""
    comps = _split_computations(hlo_text)
    entry = comps.pop("__entry__")[0]
    memo: Dict[str, Dict[str, Dict[str, float]]] = {}

    def walk(name: str) -> Dict[str, Dict[str, float]]:
        if name in memo:
            return memo[name]
        memo[name] = defaultdict(lambda: {"bytes": 0.0, "count": 0.0})
        acc = memo[name]
        for line in comps.get(name, ()):
            cm = _COLL_RE.search(line)
            if cm and not re.search(r"-done\(", line):
                acc[cm.group(2)]["bytes"] += _shape_bytes(cm.group(1))
                acc[cm.group(2)]["count"] += 1
            if _WHILE_RE.search(line):
                bm = _BODY_RE.search(line)
                if bm:
                    trip = 1
                    tm = _TRIP_RE.search(line)
                    if tm:
                        trip = int(tm.group(1))
                    for kind, v in walk(bm.group(1)).items():
                        acc[kind]["bytes"] += trip * v["bytes"]
                        acc[kind]["count"] += trip * v["count"]
                continue
            cm2 = _CALL_RE.search(line)
            if cm2:
                for kind, v in walk(cm2.group(1)).items():
                    acc[kind]["bytes"] += v["bytes"]
                    acc[kind]["count"] += v["count"]
            cm3 = _COND_RE.search(line)
            if cm3:
                branches = [b for b in cm3.groups()[:2] if b]
                if cm3.group(3):
                    branches = [s.strip().lstrip("%")
                                for s in cm3.group(3).split(",")]
                if branches:  # upper bound: the widest branch
                    best = None
                    for b in branches:
                        w = walk(b)
                        tot = sum(v["bytes"] for v in w.values())
                        if best is None or tot > best[0]:
                            best = (tot, w)
                    for kind, v in best[1].items():
                        acc[kind]["bytes"] += v["bytes"]
                        acc[kind]["count"] += v["count"]
        memo[name] = {k: dict(v) for k, v in acc.items()}
        return memo[name]

    return walk(entry) if entry else {}


def collective_stats_flat(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Line-scan without loop scaling (each op counted once)."""
    stats: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"bytes": 0.0, "count": 0.0})
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m and not re.search(r"-done\(", line):
            stats[m.group(2)]["bytes"] += _shape_bytes(m.group(1))
            stats[m.group(2)]["count"] += 1
    return dict(stats)


def all_reduces(hlo_text: str) -> List[Tuple[str, List[Tuple[int, ...]]]]:
    """-> [(computation, [dims of each result])] for every all-reduce of a
    compiled HLO text.

    Where it can, the TPU compiler fuses an all-reduce of which each
    device keeps a slice into one reduce-scatter: that all-reduce sits in
    a computation named ``all-reduce-scatter...``.  An all-reduce
    anywhere else is a full one.
    """
    comps = _split_computations(hlo_text)
    comps.pop("__entry__")
    out = []
    for comp, lines in comps.items():
        for line in lines:
            m = _COLL_RE.search(line)
            if m and m.group(2) == "all-reduce" and "-done(" not in line:
                shapes = _SHAPE_RE.findall(m.group(1))
                out.append((comp, [tuple(int(d) for d in dims.split(",") if d)
                                   for _, dims in shapes]))
    return out


def total_collective_bytes(hlo_text: str) -> int:
    return int(sum(v["bytes"] for v in collective_stats(hlo_text).values()))


_KERNEL_RE = re.compile(
    r"%([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=.*custom_call_target=\"tpu_custom_call\"")


def pallas_kernels(hlo_text: str) -> Dict[str, int]:
    """{kernel name: count} of the Pallas TPU kernels in compiled HLO text.

    A ``pallas_call(..., name=n)`` compiles to a ``tpu_custom_call``
    instruction named ``%n`` (plus XLA's ``.<k>`` suffixes), so this says
    which kernels survived into the program and did not fall back to jnp.
    """
    out: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _KERNEL_RE.search(line)
        if m:
            out[m.group(1)] += 1
    return dict(out)
