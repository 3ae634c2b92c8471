"""A whole run of the train job, past the harness's look for a chip, on a
CPU at a reduced size: the program's step as it is comes out correct, and
each fault a training cell can have, planted in the timed step, comes out
not correct under the cell's limits.  Among them: the data pipeline's
labels left unshifted, which the reference, cutting its own next-token
targets from the corpus, does not share.  The four-chip cases run fsdp over
four virtual devices, as ``traffic/fsdp4-train-s1024.json`` will."""
import time
import types

import jax
import pytest

from chip import bench
from chip.conftest import load
from chip.jobs import train


def _run(config, traffic, chips, fault):
    ctx = types.SimpleNamespace(
        config=config, traffic=traffic, chips=chips, seed=2**31 + 101,
        seconds=0.3, trace=False, devices=jax.devices()[:chips],
        t_start=time.perf_counter(), fault=fault)
    rec = train.run(ctx)
    assert rec["steps"] >= 1 and rec["compiles_in_window"] == 0
    return rec


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch",
                                   "unshifted_labels"])
def test_one_chip_cell(fault, tiny_config, tiny_traffic):
    rec = _run(tiny_config, tiny_traffic, 1, fault)
    correct, checks = bench.verdict(
        rec, load("limits", "qwen3-0.6b.train-s256x4.json"))
    assert correct == (fault is None), checks


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "no_exchange"])
def test_four_chip_fsdp(fault, tiny_fsdp4):
    """fsdp over four devices, which the four-chip cell will run: each of
    its faults fails even the one-chip cell's limits."""
    rec = _run(*tiny_fsdp4, 4, fault)
    correct, checks = bench.verdict(
        rec, load("limits", "qwen3-0.6b.train-s1024.json"))
    assert not correct, checks
