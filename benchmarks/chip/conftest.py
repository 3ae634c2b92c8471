"""Small CPU-sized cells for the benchmark's own tests."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch.devices import force_host_device_count  # noqa: E402

force_host_device_count(8)   # the four-chip cell's tests mesh over four


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture
def tiny_config():
    """qwen3-0.6b's file with its widths cut to a CPU test's size."""
    cfg = load("configs", "qwen3-0.6b.json")
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=2, num_key_value_heads=1, head_dim=32,
               vocab_size=256)
    return cfg


@pytest.fixture
def tiny_fsdp4():
    """qwen2-1.5b's file and fsdp4-train-s1024 at a CPU test's size."""
    cfg = load("configs", "qwen2-1.5b.json")
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=2, num_key_value_heads=1, vocab_size=256)
    t = load("traffic", "fsdp4-train-s1024.json")
    t.update(name="tiny", seq_len=32, global_batch=4)
    t["runtime"] = dict(t["runtime"], attn_impl="jnp", norm_impl="jnp")
    return cfg, t


@pytest.fixture
def tiny_pp2tp2():
    """qwen2-1.5b's file and pp2tp2-train-s1024 at a CPU test's size, with
    two key-value heads for the two chips of a stage to split."""
    cfg = load("configs", "qwen2-1.5b.json")
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=2, num_key_value_heads=2, vocab_size=256)
    t = load("traffic", "pp2tp2-train-s1024.json")
    t.update(name="tiny", seq_len=32, global_batch=4)
    t["runtime"] = dict(t["runtime"], attn_impl="jnp", norm_impl="jnp")
    return cfg, t


@pytest.fixture
def tiny_traffic():
    """train-s256x4 at seq 32 x batch 2 on the jnp attention and norms
    (the Pallas kernels run in interpret mode on a CPU, too slowly here)."""
    t = load("traffic", "train-s256x4.json")
    t.update(name="tiny", seq_len=32, global_batch=2)
    t["runtime"] = dict(t["runtime"], attn_impl="jnp", norm_impl="jnp")
    return t
