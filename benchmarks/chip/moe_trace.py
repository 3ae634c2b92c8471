"""Device time of the routed experts' parts, from the trace and the
compiled step (no program code).

``scope_ms`` sums the ops under one of the program's named scopes inside
the step's parts (``moe_dispatch`` inside ``blocks``: the router, top-k,
sort, permute, un-permute and weighted combine), forward and backward
together.  As in ``scopes.py``, the map from instruction to scope comes
from the cell's step compiled again from abstract shapes; an instruction
without a path of the program's (a fusion the compiler made) takes what
most instructions of the computations it calls have.  The grouped
matrix products are found by kernel name: megablox's ``gmm`` (forward
and input gradient) and ``tgmm`` (weight gradient) Pallas kernels.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Optional, Set

from chip import scopes, trace

GROUPED_MATMUL_RE = r"^t?gmm(\.\d+)?$"
_WRAPPED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")
_TEXTS: Dict[str, str] = {}


def _has_scope(op_name: str, scope: str) -> bool:
    for seg in op_name.split("/"):
        m = _WRAPPED.match(seg)
        while m:
            seg = m.group(1)
            m = _WRAPPED.match(seg)
        if seg == scope:
            return True
    return False


def scope_instructions(hlo_text: str, scope: str) -> Set[str]:
    """Names of the compiled step's instructions under ``scope``."""
    instrs = scopes.instructions(hlo_text)
    by_comp = defaultdict(list)
    for n, i in instrs.items():
        by_comp[i["computation"]].append(i)
    out = set()
    for n, i in instrs.items():
        if "/" in i["op_name"]:
            inside = _has_scope(i["op_name"], scope)
        else:
            votes = [_has_scope(c["op_name"], scope)
                     for comp in i["calls"] for c in by_comp.get(comp, ())
                     if "/" in c["op_name"]]
            inside = bool(votes) and 2 * sum(votes) > len(votes)
        if inside:
            out.add(n)
    return out


def scope_ms(run: dict, scope: str) -> Optional[float]:
    """ms a step (mean over chips) of the traced ops under ``scope``; None
    without a trace or where no op falls in it."""
    tr, rec = run["trace"], run["record"]
    if not tr or not rec.get("traced_steps"):
        return None
    if run["workload"] not in _TEXTS:
        _TEXTS[run["workload"]] = scopes.compiled_text(
            run["config"], run["traffic"], run["chips"])
    names = scope_instructions(_TEXTS[run["workload"]], scope)
    total = sum(s for d in tr["devices"].values()
                for n, s in d["ops"].items()
                if n in names and not trace.CONTAINER_RE.match(n))
    if not total:
        return None
    return 1e3 * total / len(tr["devices"]) / rec["traced_steps"]


def grouped_matmul_seconds(run: dict) -> Optional[float]:
    """Seconds a step (mean over chips) of the grouped matmul kernels."""
    tr, rec = run["trace"], run["record"]
    if not tr:
        return None
    return trace.seconds_per_step(tr, GROUPED_MATMUL_RE,
                                  rec.get("traced_steps", 0))
