"""Training job of a sparse-expert cell: ``train``'s job, plus what the
held experts computed.

The program's step returns ``expert_items`` among its metrics: the routed
items the held experts computed in all the expert layers of that step.
This job runs ``train.run`` unchanged, with the compiled step wrapped so
that each step's count is kept (a device scalar, read after the window),
and adds to the record the mean count over the timed steps (the traced
ones in a ``--trace 1`` run) and, from it, the grouped matrix products'
operations and bytes (the reference module's ``expert_matmul_cost``).
"""
from __future__ import annotations

import contextlib

from chip.jobs import train

calibrate = train.calibrate


class TrainJob(train.TrainJob):
    """``train.TrainJob`` whose compiled step keeps each step's
    ``expert_items``."""

    def compile(self, params, opt_state, batch):
        super().compile(params, opt_state, batch)
        step = self.compiled
        self.expert_items = []

        def counted(params, opt_state, batch):
            out = step(params, opt_state, batch)
            self.expert_items.append(out[2]["expert_items"])
            return out
        self.compiled = counted


@contextlib.contextmanager
def _counting(jobs: list):
    """``train.run`` builds this module's TrainJob, each listed in
    ``jobs``, while the block runs."""
    class Listed(TrainJob):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            jobs.append(self)
    plain, train.TrainJob = train.TrainJob, Listed
    try:
        yield
    finally:
        train.TrainJob = plain


def run(ctx) -> dict:
    """One benchmark run of a sparse-expert training cell; -> ``train.run``'s
    record with ``expert_items_per_step`` and ``expert_matmul_cost``."""
    jobs: list = []
    with _counting(jobs):
        rec = train.run(ctx)
    job, = jobs
    timed = rec.get("traced_steps") or rec["steps"]
    counts = [float(x) for x in job.expert_items[-timed:]]
    items = sum(counts) / len(counts)
    rec["expert_items_per_step"] = items
    rec["expert_matmul_cost"] = job.model.expert_matmul_cost(ctx.config,
                                                             items)
    train.log(f"[experts] {items:.1f} routed items a step through the held "
              f"experts (timed steps {min(counts):.0f} to "
              f"{max(counts):.0f})")
    return rec
