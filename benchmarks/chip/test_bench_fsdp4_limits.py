"""The four-chip cell's own limits, set from its chip readings, on a CPU
at a reduced size: each fault planted in the timed step, fsdp over four
virtual devices, comes out not correct."""
import time
import types

import jax
import pytest

from chip import bench
from chip.conftest import load
from chip.jobs import train


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "no_exchange"])
def test_faults_fail_the_fsdp4_cell_limits(fault, tiny_fsdp4):
    config, traffic = tiny_fsdp4
    ctx = types.SimpleNamespace(
        config=config, traffic=traffic, chips=4, seed=2**31 + 103,
        seconds=0.3, trace=False, devices=jax.devices()[:4],
        t_start=time.perf_counter(), fault=fault)
    rec = train.run(ctx)
    correct, checks = bench.verdict(
        rec, load("limits", "qwen2-1.5b.fsdp4-train-s1024.json"))
    assert not correct, checks
