#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 1,2,3

In one process, on the cell's chips and at its sizes, the job that the
cell's traffic file names (``jobs/<job>.py``, as ``bench.py`` finds it)
gives its readings (its ``calibrate``): for each seed of ``--seeds`` the
program against the plain reference (the lower readings), and for each of
``--control-seeds`` the control and the planted faults against the same
reference (the upper readings).  Prints one JSON line per reading and
writes them all to ``--out``.  The benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def job_of(files: dict):
    """The job module of a cell's files (``bench.cell_files``)."""
    from chip import bench
    return bench._load_module(files["job"],
                              "bench_job_" + files["traffic"]["job"])


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
    files = bench.cell_files(bench._read_json(ROOT, "BENCHMARK.json"),
                             args.workload)
    chips = files["cell"]["chips"]
    bench.check_device(chips)
    rows = []
    for row in job_of(files).calibrate(files["config"], files["traffic"],
                                       chips, seeds, control):
        rows.append(row)
        print(json.dumps(row), flush=True)
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
