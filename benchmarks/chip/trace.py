"""Profiler trace -> device busy time, kernel time, exposed collectives.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the device
operations of each TPU core (the "XLA Ops" line of each ``/device:TPU:<n>``
plane) and the host spans the benchmark records (``bench/*``
TraceAnnotations).  ``reduce`` cuts both to the traced window (the first
``bench/dispatch`` to the end of the last ``bench/sync``) and sums them.
Both work on plain tuples, so a test can hand ``reduce`` a small trace of
its own.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

Interval = Tuple[float, float]
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
# control flow whose events enclose the ops of its body: listed per device,
# but left out of busy, compute and exposed-collective time (a `while`
# around a layer scan would cover its collectives and idle stretches) and
# out of the ranking of ops
CONTAINER_RE = re.compile(r"^(while|conditional|call)\b")
COLLECTIVE_RE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv", re.IGNORECASE)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir},"
                                f" found {len(paths)}")
    return paths[0]


def load(path: str) -> dict:
    """-> {"devices": {id: [(name, start_ns, end_ns), ...]},
           "host": [(name, start_ns, end_ns), ...]}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = _DEVICE_RE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
            devices[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def op_name(event_name: str) -> str:
    """The HLO instruction's own name: a TPU op event is named by its whole
    instruction text ("%fusion.3 = f32[...] fusion(%flash_attention_fwd.7,
    ...)"), whose operands name other ops."""
    return event_name.split(" = ", 1)[0].lstrip("%")


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> List[Interval]:
    """Merge overlapping intervals; -> sorted, disjoint."""
    out: List[list] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals, cover) -> float:
    """Length of ``intervals`` (disjoint, sorted) not covered by ``cover``
    (disjoint, sorted)."""
    total, j = 0.0, 0
    for a, b in intervals:
        t = a
        while j < len(cover) and cover[j][1] <= t:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > t:
                total += cover[k][0] - t
            t = max(t, cover[k][1])
            k += 1
        if t < b:
            total += b - t
    return total


def gaps(busy, lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] between ``busy`` intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def window_of(host, devices) -> Interval:
    starts = [a for n, a, _ in host if n == SPAN_PREFIX + "dispatch"]
    ends = [b for n, _, b in host if n == SPAN_PREFIX + "sync"]
    if starts and ends:
        return min(starts), max(ends)
    ops = [e for evs in devices.values() for e in evs]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(a for _, a, _ in ops), max(b for _, _, b in ops)


def _host_at(host_sorted, t: float) -> str:
    """The innermost bench span open at ``t`` (the latest to start)."""
    name = "none"
    for n, a, b in host_sorted:
        if a > t:
            break
        if b >= t:
            name = n
    return name


def reduce(loaded: dict, n_devices: int = 0, top: int = 10) -> dict:
    """Cut the trace to its window and sum it per device.

    -> {"window_s", "devices": {id: {"busy_s", "ops": {name: s},
        "collective_s", "collective_exposed_s"}}, "device_ops": [[name, s]],
        "idle_gaps": [[span, s]]}; per-device sums are over the window,
    busy, compute and collective time count leaf operations only;
    ``device_ops`` averages over the devices and leaves out control flow.
    """
    devices = {d: evs for d, evs in loaded["devices"].items() if evs}
    if n_devices:
        devices = {d: devices[d] for d in sorted(devices)[:n_devices]}
    if not devices:
        raise ValueError("the trace holds no device operation")
    host = sorted(loaded["host"], key=lambda e: e[1])
    lo, hi = window_of(host, devices)
    out: Dict[int, dict] = {}
    op_total: Dict[str, float] = defaultdict(float)
    idle: List[list] = []
    for d, evs in devices.items():
        ops: Dict[str, float] = defaultdict(float)
        comm, comp = [], []
        for name, a, b in evs:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            ops[name] += (b - a) * 1e-9
            if CONTAINER_RE.match(name):
                continue
            (comm if COLLECTIVE_RE.search(name) else comp).append((a, b))
        busy = union(comm + comp)
        comm_u = union(comm)
        out[d] = {"busy_s": length(busy) * 1e-9, "ops": dict(ops),
                  "collective_s": length(comm_u) * 1e-9,
                  "collective_exposed_s": subtract(comm_u, union(comp)) * 1e-9}
        for name, s in ops.items():
            if not CONTAINER_RE.match(name):
                op_total[name] += s / len(devices)
        for a, b in gaps(busy, lo, hi):
            idle.append([_host_at(host, (a + b) / 2), (b - a) * 1e-9])
    idle.sort(key=lambda x: -x[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": out,
        "device_ops": sorted(([n, s] for n, s in op_total.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": idle[:top],
    }


def kernel_seconds(reduced: dict, pattern: str) -> Dict[int, float]:
    """Per device, the summed time of the ops whose name matches."""
    rx = re.compile(pattern)
    return {d: sum(s for n, s in v["ops"].items() if rx.search(n))
            for d, v in reduced["devices"].items()}


def seconds_per_step(reduced: dict, pattern: str, steps: int):
    """Mean over the chips of the matching ops' time per step; None where
    no op matches."""
    per_dev = kernel_seconds(reduced, pattern)
    total = sum(per_dev.values()) / len(per_dev)
    return total / steps if total > 0 and steps else None
