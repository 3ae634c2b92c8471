"""The pipelined four-chip cell on a CPU at a reduced size, pp2 x tp2 1F1B
over four virtual devices: the program's step in float32 agrees with the
plain reference to round-off, and each fault planted in the timed step,
the exchange between the chips of a stage among them, comes out not
correct under the cell's limits."""
import time
import types

import jax
import pytest

from chip import bench
from chip import reference_base as base
from chip.conftest import load
from chip.jobs import train

LIMITS = "qwen2-1.5b.pp2tp2-train-s1024.json"


def test_reference_matches_pp2tp2_in_float32(tiny_pp2tp2):
    config, traffic = tiny_pp2tp2
    job = train.TrainJob(
        config, dict(traffic, strategy="fsdp_tp2_pp2_mb4_1f1b_f32"), 4)
    assert job.mesh_shape == {"pipe": 2, "data": 1, "model": 2}
    seed = 2**31 + 13
    *_, got, corpus = job.check_steps(seed)
    gaps = base.compare(got, job.reference(seed, corpus))
    assert gaps["loss_gap"][0] < 2e-5, gaps
    assert gaps["grad_norm_gap"][0] < 1e-4, gaps
    assert gaps["update_norm_gap"][0] < 1e-4, gaps


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "no_exchange"])
def test_faults_fail_the_pp2tp2_cell_limits(fault, tiny_pp2tp2):
    config, traffic = tiny_pp2tp2
    ctx = types.SimpleNamespace(
        config=config, traffic=traffic, chips=4, seed=2**31 + 107,
        seconds=0.3, trace=False, devices=jax.devices()[:4],
        t_start=time.perf_counter(), fault=fault)
    rec = train.run(ctx)
    assert rec["steps"] >= 1 and rec["compiles_in_window"] == 0
    correct, checks = bench.verdict(rec, load("limits", LIMITS))
    assert not correct, checks
