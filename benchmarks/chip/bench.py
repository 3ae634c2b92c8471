#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything is found by name.  The cell's entry in ``BENCHMARK.json``
names its configuration (``configs/<config>.json``, the published
config.json plus the program's registry name and the module of its plain
reference, ``<reference>.py``) and its traffic
(``traffic/<traffic>.json``, which names the job kind,
``jobs/<kind>.py``); ``limits/<cell>.json`` holds the limits of the
comparison that decides ``correct``; each per-layer metric is read by
``metrics/<metric>.py``.  The peaks of each chip are in ``peaks.json``.

A run loads, warms up (the cell's own shapes only, from JAX's persistent
compilation cache in ``<checkout>/.jax_cache`` after the first run),
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints one JSON line last.  With ``--trace 1``
the last seconds of the window are traced and the line carries the
per-layer metrics instead of the end-to-end ones.  A host whose JAX finds
no TPU, too few chips, or a chip missing from ``peaks.json`` exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class BenchError(RuntimeError):
    """A run that cannot give a result: wrong host or broken cell files."""


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"no such file {os.path.relpath(path, ROOT)}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    if not os.path.exists(path):
        raise BenchError(f"no such file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def cell_files(bench: dict, workload: str) -> dict:
    """The cell's entries and files, found by the names in BENCHMARK.json."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    traffic = _read_json(HERE, "traffic", cell["traffic"] + ".json")
    traffic.setdefault("name", cell["traffic"])
    return {
        "cell": cell,
        "config": _read_json(ROOT, entry["file"]),
        "traffic": traffic,
        "limits": _read_json(HERE, "limits", workload + ".json"),
        "job": os.path.join(HERE, "jobs", traffic["job"] + ".py"),
    }


def applies(metric: dict, workload: str, e2e_names=None) -> bool:
    """Whether the cell reports the metric: the cells its ``workloads``
    list; without one, every cell (an end-to-end metric, ``e2e_names``
    None) or every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def check_device(chips: int):
    """-> (devices, device kind, peaks of that kind); BenchError if the
    host has no TPU, too few chips, or a chip the peaks table lacks."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    print(f"[device] platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {d0.platform})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices, d0.device_kind, peak_of(d0.device_kind)


def peak_of(kind: str) -> dict:
    peaks = _read_json(HERE, "peaks.json")
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in peaks.json "
                         f"(known: {sorted(peaks)})")
    return peaks[kind]


def verdict(rec: dict, limits: dict):
    """-> (correct, each compared number beside its limit)."""
    checks = {}
    for name, limit in limits["limits"].items():
        value, where = rec["compare"][name]
        checks[name] = {"value": value, "limit": limit, "at": where}
    correct = rec["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return correct, checks


def result_line(bench, files, rec, devices, kind, peak, args):
    """-> (the result line, the checks behind its `correct`)."""
    workload = args.workload
    cell = files["cell"]
    e2e = [m for m in bench["end_to_end"] if applies(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    if args.trace:
        metrics = {}
        run = {"record": rec, "trace": rec.get("trace"),
               "config": files["config"], "traffic": files["traffic"],
               "peak": peak, "chips": cell["chips"], "workload": workload}
        for m in bench["per_layer"]:
            if not applies(m, workload, e2e_names):
                continue
            reader = _load_module(os.path.join(HERE, "metrics",
                                               m["name"] + ".py"),
                                  "bench_metric_" + m["name"])
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": rec["e2e"][m["name"]],
                               "unit": m["unit"]} for m in e2e}
    correct, checks = verdict(rec, files["limits"])
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": rec["attempted"],
            "failed": rec["failed"], "metrics": metrics, "device": device}
    tr = rec.get("trace")
    if tr is not None:
        busy = [d["busy_s"] for d in tr["devices"].values()]
        device.update(busy_s=sum(busy) / len(busy), window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    return line, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    try:
        bench = _read_json(ROOT, "BENCHMARK.json")
        files = cell_files(bench, args.workload)
        chips = files["cell"]["chips"]
        devices, kind, peak = check_device(chips)
        job = _load_module(files["job"], "bench_job_" + files["traffic"]["job"])
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    ctx = types.SimpleNamespace(
        config=files["config"], traffic=files["traffic"], chips=chips,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices[:chips], t_start=T_START, fault=None)
    rec = job.run(ctx)
    line, checks = result_line(bench, files, rec, devices, kind, peak, args)
    for name, c in checks.items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:.6g}, "
              f"worst at {c['at']})", file=sys.stderr, flush=True)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


def setup_paths() -> None:
    """Import the benchmark as the package ``chip`` and the program from
    ``src``; keep the compile cache at one fixed path in the checkout."""
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
    sys.path[:0] = [os.path.dirname(HERE), os.path.join(ROOT, "src")]
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


if __name__ == "__main__":
    setup_paths()
    sys.exit(main())
