"""The plain reference against the program's own train step, on a CPU at
a reduced size: in float32 they agree to round-off over the checked steps,
and the reference's narrow-precision control does not."""
import jax
import numpy as np
import pytest

from chip import reference_base as base
from chip.jobs.train import TrainJob


@pytest.fixture
def f32_job(tiny_config, tiny_traffic):
    return TrainJob(tiny_config, dict(tiny_traffic, strategy="fsdp_f32"), 1)


def test_reference_matches_the_program_step_in_float32(f32_job):
    seed = 2**31 + 5
    *_, got, corpus = f32_job.check_steps(seed)
    want = f32_job.reference(seed, corpus)
    gaps = base.compare(got, want)
    assert gaps["loss_gap"][0] < 2e-5, gaps
    assert gaps["grad_norm_gap"][0] < 1e-4, gaps
    assert gaps["update_norm_gap"][0] < 1e-4, gaps
    # the optimizer moved every leaf the reference moves
    assert gaps["left_out"] == []
    assert all(v > 0 for v in got["update_norms"].values())


def test_reference_matches_fsdp_over_four_devices_in_float32(tiny_fsdp4):
    config, traffic = tiny_fsdp4
    job = TrainJob(config, dict(traffic, strategy="fsdp_f32"), 4)
    seed = 2**31 + 3
    *_, got, corpus = job.check_steps(seed)
    gaps = base.compare(got, job.reference(seed, corpus))
    assert gaps["loss_gap"][0] < 2e-5, gaps
    assert gaps["grad_norm_gap"][0] < 1e-4, gaps
    assert gaps["update_norm_gap"][0] < 1e-4, gaps


def test_weights_come_from_the_seed(tiny_config):
    init = base.for_config(tiny_config).init_weights
    a = init(tiny_config, base.seed_key(2**33 + 1))
    b = init(tiny_config, base.seed_key(2**33 + 1))
    c = init(tiny_config, base.seed_key(1))
    la, lb, lc = (jax.tree.leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(a["embed"]["tok"], c["embed"]["tok"])


def test_reference_rows_match_the_program_batcher(f32_job):
    """The reference's own next-token rows are the Batcher's, wrapping
    round the corpus, and the corpus comes from the seed alone."""
    seed = 2**32 + 9
    corpus = f32_job.corpus(seed)
    assert np.array_equal(corpus, f32_job.corpus(seed))
    assert not np.array_equal(corpus, f32_job.corpus(seed + 1))
    n = len(corpus) // (f32_job.seq * f32_job.batch) + 2   # past the wrap
    it = f32_job.batches(corpus)
    for tokens, targets in base.rows(corpus, f32_job.seq,
                                          f32_job.batch, n):
        b = next(it)
        assert np.array_equal(b["tokens"], tokens)
        assert np.array_equal(b["labels"], targets)
        assert np.array_equal(targets[:, :-1], tokens[:, 1:])


def test_non_finite_program_numbers_read_as_infinite_gaps():
    want = {"losses": [1.0], "grad_norms": {"a": 1.0, "b": 2.0},
            "update_norms": {"a": 1.0, "b": 2.0}}
    got = {"losses": [float("nan")],
           "grad_norms": {"a": float("nan"), "b": 2.0},
           "update_norms": {"a": 1.0, "b": float("inf")}}
    gaps = base.compare(got, want)
    assert all(gaps[k][0] == float("inf")
               for k in ("loss_gap", "grad_norm_gap", "update_norm_gap"))


def test_narrow_dot_rounds_operands():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    exact = base.make_dot()("ij,jk->ik", x, x)
    fp8 = base.make_dot("float8_e4m3fn")("ij,jk->ik", x, x)
    rel = float(np.abs(fp8 - exact).max() / np.abs(exact).max())
    assert 1e-3 < rel < 0.2


@pytest.mark.parametrize("cell", ["qwen3-0.6b.train-s1024",
                                  "qwen3-0.6b.train-s256x4"])
def test_control_in_fp8_is_not_correct(cell, tiny_config, tiny_traffic):
    """The control, the reference with every matrix product in fp8 put in
    the program's place, fails the cell's limits at a test's size too."""
    from chip import bench
    from chip.conftest import load
    job = TrainJob(tiny_config, tiny_traffic, 1)
    for seed in (2**31 + 7, 2**31 + 8):
        corpus = job.corpus(seed)
        want = job.reference(seed, corpus)
        got = job.reference(seed, corpus, dot_dtype="float8_e4m3fn")
        rec = {"compare": base.compare(got, want), "failed": 0}
        correct, checks = bench.verdict(rec, load("limits", cell + ".json"))
        assert not correct, checks
