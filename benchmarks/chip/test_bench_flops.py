"""The yardstick's counts, its peaks table and its refusal of a host
without a TPU."""
import json
import os
import subprocess
import sys

import jax
import pytest

from chip import bench, flops
from chip.conftest import HERE, ROOT, load
from chip.reference_base import for_config

MATMULS = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "tok")


@pytest.mark.parametrize("name,published", [("qwen3-0.6b", 0.596e9),
                                            ("qwen2-1.5b", 1.544e9)])
def test_matmul_params_match_the_program_layout(name, published):
    """N from the config's sizes equals the count of the program's own
    projection and (tied) embedding weights."""
    from repro.models import transformer as tfm
    config = load("configs", name + ".json")
    model = for_config(config)
    cfg, _ = model.program_config(config)
    shapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    n = sum(leaf.size for path, leaf in flat
            if getattr(path[-1], "key", "") in MATMULS)
    assert model.matmul_params(config) == n
    assert abs(n - published) / published < 0.01


def test_train_flops_per_token_counts_causal_attention():
    config = load("configs", "qwen3-0.6b.json")
    model = for_config(config)
    n = model.matmul_params(config)
    att = 12 * 28 * (1024 + 1) / 2 * 16 * 128
    assert model.train_flops_per_token(config, 1024) == 6 * n + att
    assert 3.9e9 < model.train_flops_per_token(config, 1024) < 3.95e9


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline(100.0, 1.0, 2.0, peak) == (50.0, "compute")
    assert flops.roofline(1.0, 100.0, 20.0, peak) == (50.0, "memory")


def test_unknown_device_kind_raises():
    assert bench.peak_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(bench.BenchError):
        bench.peak_of("TPU v99")


def test_a_host_without_tpu_exits_nonzero_with_no_result():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "bench.py"), "--workload", cell,
         "--seed", str(2**31 + 17), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout
