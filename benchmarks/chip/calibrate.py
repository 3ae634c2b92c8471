#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 1,2,3

In one process, on the cell's chips and at its sizes: for each seed of
``--seeds`` the program's checked steps against the plain float32
reference (the lower readings); for each of ``--control-seeds`` the
control (the reference with every matrix product in float8_e4m3fn, put in
the program's place) and the planted fault "half the batch left out" (the
reference over half the batch, put in the program's place) against the
same reference (the upper readings); on several chips also the fault
"exchange between chips left out".  A state left unchanged reads 1 by
construction and needs no run.  Prints one JSON line per reading and
writes them all to ``--out``.  The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]

    from chip import bench
    from chip.jobs.train import TrainJob
    from chip.reference import compare

    files = bench.cell_files(bench._read_json(ROOT, "BENCHMARK.json"),
                             args.workload)
    chips = files["cell"]["chips"]
    bench.check_device(chips)
    job = TrainJob(files["config"], files["traffic"], chips)
    rows = []

    def emit(kind, seed, got, want):
        gaps = compare(got, want)
        row = {"kind": kind, "seed": seed,
               **{k: gaps[k][0] for k in ("loss_gap", "grad_norm_gap",
                                          "update_norm_gap")},
               "at": {k: gaps[k][1] for k in ("loss_gap", "grad_norm_gap",
                                              "update_norm_gap")},
               "left_out": gaps["left_out"],
               "losses": got["losses"], "ref_losses": want["losses"]}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for seed in seeds:
        params, opt_state, batch, it, got, corpus = job.check_steps(seed)
        del params, opt_state, batch, it
        want = job.reference(seed, corpus)
        emit("program", seed, got, want)
        if seed not in control:
            continue
        emit("control_fp8", seed,
             job.reference(seed, corpus, dot_dtype="float8_e4m3fn"), want)
        emit("fault_half_batch", seed,
             job.reference(seed, corpus, fault="half_batch"), want)
        if chips > 1:
            emit("fault_no_exchange", seed,
                 job.reference(seed, corpus, fault="no_exchange"), want)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
    sys.path.insert(0, os.path.dirname(HERE))
    from chip import bench
    bench.setup_paths()
    sys.exit(main())
