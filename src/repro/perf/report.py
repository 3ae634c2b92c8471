"""Assemble EXPERIMENTS.md from dry-run records, roofline analysis, and
benchmark CSVs.  §Perf prose lives in results/perf_log.md (hand-written
during the hillclimb iterations) and is inlined verbatim.

    PYTHONPATH=src python -m repro.perf.report > EXPERIMENTS.md

All ``results/...`` inputs resolve against the repo root (perf/paths.py),
so the report builds identically from any working directory; a build
that matches **zero** ok dry-run records exits non-zero instead of
silently emitting empty tables.
"""
from __future__ import annotations

import csv
import glob
import json
import os
import sys

from repro.perf import roofline
from repro.perf.paths import results_path

# counts every ok dryrun record seen while building; main() refuses to
# emit a report built from nothing
_N_OK_DRYRUN = 0


def _dryrun_table(mesh: str) -> str:
    global _N_OK_DRYRUN
    rows = []
    for path in sorted(glob.glob(results_path("dryrun",
                                              f"*_{mesh}.json"))):
        with open(path) as f:
            r = json.load(f)
        if r["status"] == "skipped":
            rows.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | "
                        f"skipped: {r['reason']} |")
            continue
        if r["status"] != "ok":
            rows.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | "
                        f"**ERROR** {r.get('error','')[:80]} |")
            continue
        _N_OK_DRYRUN += 1
        mem = r["memory"]
        per_dev_gib = (mem["argument_bytes_per_device"]
                       + mem["temp_bytes_per_device"]) / 2**30
        coll = r.get("collective_bytes_total", 0)
        plan = r.get("plan", {})
        rows.append(
            f"| {r['arch']} | {r['shape']} | {plan.get('attn','?')} "
            f"| {r['compile_s']:.0f}s | {per_dev_gib:.1f} "
            f"| {coll:.2e} | ok |")
    hdr = ("| arch | shape | attn plan | compile | bytes/dev GiB | "
           "collective B | status |\n|---|---|---|---|---|---|---|\n")
    return hdr + "\n".join(rows) + "\n"


def _collective_detail(mesh: str) -> str:
    out = ["| arch | shape | all-gather | all-reduce | reduce-scatter | "
           "all-to-all | collective-permute |",
           "|---|---|---|---|---|---|---|"]
    for path in sorted(glob.glob(results_path("dryrun",
                                              f"*_{mesh}.json"))):
        with open(path) as f:
            r = json.load(f)
        if r.get("status") != "ok":
            continue
        c = r.get("collectives", {})

        def b(k):
            v = c.get(k, {}).get("bytes", 0)
            return f"{v:.2e}" if v else "0"
        out.append(f"| {r['arch']} | {r['shape']} | {b('all-gather')} | "
                   f"{b('all-reduce')} | {b('reduce-scatter')} | "
                   f"{b('all-to-all')} | {b('collective-permute')} |")
    return "\n".join(out) + "\n"


def _benchmark_summaries() -> str:
    out = []
    for path in sorted(glob.glob(results_path("benchmarks", "*.csv"))):
        name = os.path.basename(path)[:-4]
        with open(path) as f:
            rows = list(csv.reader(f))
        out.append(f"### {name}\n")
        out.append("| " + " | ".join(rows[0]) + " |")
        out.append("|" + "---|" * len(rows[0]))
        for row in rows[1:]:
            out.append("| " + " | ".join(row) + " |")
        out.append("")
    return "\n".join(out) + "\n"


def _pipeline_sweep() -> str:
    """§Schedule-frontier table from BENCH_pipeline.json: the extended
    pp x {gpipe,1f1b,1f1b_i<v>,zb} x overlap sweep with the analytic
    per-schedule bubble and peak memory (cost model + compiled-executable
    memory analysis)."""
    path = results_path("benchmarks", "BENCH_pipeline.json")
    if not os.path.exists(path):
        return "_(run `python benchmarks/run.py --pp-sweep` first)_\n"
    with open(path) as f:
        bench = json.load(f)

    def _mib(v):
        return f"{v / 2**20:.0f}" if v is not None else "—"

    def _frac(v):
        return f"{v:.3f}" if v is not None else "—"

    out = [f"Backend `{bench.get('backend', '?')}`, "
           f"arch `{bench.get('arch', '?')}`.  Wall time on CPU hosts is a "
           "regression signal; the schedule-comparable columns are the "
           "bubble fraction (hardware-free) and the peak-memory pair — "
           "predicted (cost model in-flight term) next to measured "
           "(compiled executable temp bytes).\n",
           "| spec | sched | v | ovl | bubble pred | "
           "mem pred MiB | mem meas MiB |",
           "|---|---|---|---|---|---|---|"]
    for r in bench.get("rows", []):
        out.append(
            f"| {r['spec']} | {r.get('sched', '—')} "
            f"| {r.get('virtual_stages', 1)} "
            f"| {'on' if r.get('overlap') else 'off'} "
            f"| {_frac(r.get('bubble_predicted'))} "
            f"| {_mib(r.get('predicted_peak_memory_bytes'))} "
            f"| {_mib(r.get('measured_temp_bytes'))} |")
    out.append("\n`—` means the backend reported no executable memory "
               "analysis.\n")
    return "\n".join(out) + "\n"


def _perf_log() -> str:
    path = results_path("perf_log.md")
    if os.path.exists(path):
        with open(path) as f:
            return f.read()
    return "_(perf iteration log pending)_\n"


HEADER = """# EXPERIMENTS

Reproduction of **Hardware Scaling Trends and Diminishing Returns in
Large-Scale Distributed Training** (Fernandez et al., 2024) on the TPU v5e
target (256-chip pod / 2-pod meshes), CPU-validated.  See DESIGN.md for the
architecture of the framework and the GPU->TPU adaptation; this file holds
the experimental evidence.

Hardware constants for all derived numbers: 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI per chip; v5e HBM capacity 16 GiB.

## §Paper-claims — cost-model reproduction of the paper's findings

The analytical cost model (`core/costmodel.py`) was calibrated once
(kernel efficiencies + inter-node latency + prefetch depth; see DESIGN.md)
and then evaluated against the paper's headline numbers:

| claim (paper §) | paper | this repro | status |
|---|---|---|---|
| Weak scaling: TFLOPS/WPS drop, 128->2048 H100s (§4.1) | −37.22% | −38.6% | ✅ |
| Per-GPU power nearly flat over the same sweep (§4.1) | −5.87% (658→620 W) | −5.8% | ✅ |
| TP 2–4 beats pure FSDP at 2048 GPUs, WPS gain (§5) | +52.6% | +46.6% (tp=4) | ✅ (direction + magnitude) |
| Optimal-strategy MFU, H100 256 GPUs (§4.4) | 40.77% | 45.3% | ✅ (≈) |
| Optimal-strategy MFU, A100 256 GPUs (§4.4) | 59.67% | 59.4% | ✅ |
| FSDP unavoidably comm-bound beyond ~128 GPUs (§5) | qualitative | exposed comm 0 at 8 GPUs, grows monotonically past 128 | ✅ |
| AllGather ring busbw decays with world size; tree AllReduce does not (Fig 2) | qualitative | property-tested (`test_costmodel.py`) | ✅ |
| Longer context -> better overlap, higher MFU & power efficiency (§4.6) | qualitative | reproduced (fig9 benchmark) | ✅ |
| Memory per GPU falls with DP degree, saturating (Fig 14) | qualitative | reproduced (fig14 benchmark) | ✅ |

Residuals: (a) the model reproduces the 2048-GPU TP flip but at 256 GPUs
its optimum stays at tp=1 (paper Fig 6 already sees tp=2 winning at 256);
(b) the exposure knee sits at ~1024 GPUs rather than just past 128 — the
calibration concentrates the measured 128→2048 throughput drop near the
latency-bound transition.  Both trades buy exactness on the weak-scaling,
power, and MFU anchors.  All anchors are enforced as tests
(`tests/test_costmodel.py::test_claim_*`).

"""

SECTION_NOTES = """
Notes on conventions:
* *collective B* is the trip-count-scaled sum of collective-op result bytes
  in the compiled HLO (`perf/hlo.py`); lax.scan bodies are multiplied by
  their `known_trip_count` — a naive line scan undercounts ~n_layers x.
* FLOPs are analytic (`perf/flops.py`): XLA's `cost_analysis()` counts scan
  bodies once, so compiled-HLO FLOPs structurally undercount; the analytic
  numbers model exactly the einsums the step executes (incl. remat, MoE
  capacity slop, causal triangularity).
* *bytes/dev* = argument + temp bytes from `compiled.memory_analysis()` —
  the fit-proof against the 16 GiB v5e HBM.
"""


def main():
    parts = [HEADER]
    parts.append("## §Dry-run — 10 arch x 4 shapes on the production meshes\n")
    parts.append("Every (architecture x shape) lowers **and compiles** for "
                 "both meshes; `long_500k` is skipped for pure full-attention "
                 "archs per DESIGN.md §4 (7 documented skips).\n")
    parts.append("### Single pod: (16, 16) = 256 chips, axes (data, model)\n")
    parts.append(_dryrun_table("pod16x16"))
    parts.append(SECTION_NOTES)
    parts.append("\n### Multi-pod: (2, 16, 16) = 512 chips, axes "
                 "(pod, data, model), HSDP across pods\n")
    parts.append(_dryrun_table("pod2x16x16"))
    parts.append("""
**HSDP vs fully-sharded 2D across pods** (`--dp_mode fsdp2d`, tagged runs):
sharding params over (pod, data) instead of replicating across pods halves
persistent parameter/optimizer state (granite-20b args 0.80 → 0.40
GiB/chip; qwen3 0.05 → 0.03) at nearly identical collective volume in
the compiled HLO (granite 5.114e11 → 5.107e11 B) — *but* the FSDP gathers
then cross the DCN pod boundary, which the cost model prices ~8× slower
per rank than ICI; HSDP therefore stays the default (the paper's
hierarchical-sharding recommendation, §6), with fsdp2d available when
capacity, not bandwidth, binds.
""")
    parts.append("\n### Collective mix per pair (single pod, bytes)\n")
    parts.append(_collective_detail("pod16x16"))

    parts.append("\n## §Roofline — three-term analysis per pair "
                 "(single pod, baseline)\n")
    rows = roofline.table(mesh="pod16x16")
    parts.append(roofline.markdown(rows))
    parts.append("""
Reading the table: decode shapes are uniformly **memory-bound** (KV/state
cache + weight streaming per token — the paper's asymmetric-hardware point
applies: more FLOPs would not help), train/prefill shapes are
**compute-bound** at this scale, with collective terms between ~0.5% and
~10% of the compute term (largest for the smallest model, qwen3-0.6b —
the paper's small-per-device-workload regime; see §Perf pair 2).  A
256-chip v5e pod with FSDP x TP is therefore *not yet* communication-
bound, consistent with the paper's finding that exposure begins beyond
~128 fast-interconnect devices: the v5e pod keeps the whole FSDP group on
ICI, and the cost model's `tpu_v5e_transfer` benchmark shows the exposure
appearing across the pod (DCN) boundary instead.  `6ND/compiled` < 1
quantifies remat (+1 fwd), MoE capacity slop (cf=1.25), attention
quadratic terms, and dense-layer overheads per arch.
""")

    opt_rows = roofline.table(mesh="pod16x16", tag="opt")
    if opt_rows:
        parts.append("\n### Optimized configurations (post-§Perf, tagged `opt`)\n")
        parts.append("Paper-faithful baselines above; the beyond-paper "
                     "optimized runs (scatter-free MoE dispatch + per-arch "
                     "gradient accumulation + SP ablation) below — both "
                     "recorded separately per the methodology:\n")
        parts.append(roofline.markdown(opt_rows))

    parts.append("\n## §Perf — hillclimbing log (3 selected pairs)\n")
    parts.append(_perf_log())

    parts.append("\n## §Benchmarks — per-figure outputs (cost model)\n")
    parts.append(_benchmark_summaries())
    parts.append("\n## §Schedule-frontier — pp x schedule x overlap sweep\n")
    parts.append(_pipeline_sweep())
    parts.append("\n## §Telemetry — measured-run artifacts\n")
    parts.append(
        "Instrumented runs (`--trace`, `--metrics_jsonl`, "
        "`--drift_report`; `benchmarks/run.py --drift-report`) write "
        "under `results/telemetry/`: Chrome-trace/Perfetto JSONs of "
        "host spans, JSONL event streams (schema-checked in CI via "
        "`python -m repro.telemetry`), and drift reports comparing the "
        "cost model's per-term step-time decomposition against "
        "measured windows (`predicted_over_measured` per "
        "compute/collective/bubble term).  See README \"Observability\".\n")
    if _N_OK_DRYRUN == 0:
        print("ERROR: no ok dryrun records matched under "
              f"{results_path('dryrun')} — run "
              "`python -m repro.launch.dryrun` first (the report would "
              "be built entirely from empty tables)", file=sys.stderr)
        raise SystemExit(1)
    print("\n".join(parts))


if __name__ == "__main__":
    main()
