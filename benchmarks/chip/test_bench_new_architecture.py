"""A new architecture enters the benchmark by new files alone: its
configuration file names its own reference module, and the harness finds
the reference, the program's configuration, the job and its calibration
by the names in the files.  Shown in a copy of the benchmark, with a stub
reference of a sparse-expert decoder whose file lists cuts in `reduced`."""
import ast
import hashlib
import json
import os
import shutil
import subprocess
import sys

from chip.conftest import HERE, ROOT

CELL = "stub-moe.stub-train"

CONFIG = {
    "registry": "deepseek-moe-16b",
    "reference": "reference_stub_moe",
    "source": "https://example.org/stub-moe/config.json",
    "deployment": "each layer's experts split over eight chips, one "
                  "chip's share here",
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "published": {"num_hidden_layers": 28, "n_routed_experts": 64,
                  "vocab_size": 102400},
    "model_type": "stub_moe",
    "hidden_size": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "num_hidden_layers": 3, "vocab_size": 256,
}

STUB = '''"""Stub reference of a sparse-expert decoder at a test's size: it
builds the program's configuration and its weight layout, and makes
weights of nought; it has no layer equations."""
import dataclasses

import jax


def program_config(config):
    from repro.configs import get_config
    base = get_config(config["registry"])
    moe = dataclasses.replace(
        base.moe, n_experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        expert_d_ff=config["moe_intermediate_size"])
    cfg = dataclasses.replace(
        base, n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["moe_intermediate_size"],
        dense_d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], moe=moe)
    return cfg, {}


def program_values(cfg):
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "intermediate_size": cfg.dense_d_ff,
            "moe_intermediate_size": cfg.moe.expert_d_ff,
            "n_routed_experts": cfg.moe.n_experts,
            "num_experts_per_tok": cfg.moe.top_k,
            "n_shared_experts": cfg.moe.n_shared_experts,
            "vocab_size": cfg.vocab_size}


def weight_shapes(config):
    from repro.models import transformer as tfm
    cfg, _ = program_config(config)
    shapes = jax.eval_shape(lambda k: tfm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return jax.tree.map(lambda x: tuple(x.shape), shapes)


def init_weights(config, key):
    return jax.tree.map(lambda s: jax.numpy.zeros(s), weight_shapes(config),
                        is_leaf=lambda x: isinstance(x, tuple))
'''

# run in the copy: the harness's lookups, each by the names in the files
DRIVE = '''
import json
from chip import bench, calibrate
from chip.reference_base import for_config
files = bench.cell_files(json.load(open("BENCHMARK.json")), "%s")
job = calibrate.job_of(files)
model = for_config(files["config"])
train = job.TrainJob(files["config"], files["traffic"], 1)
print(json.dumps({"reference": model.__file__, "model": model.__name__,
                  "experts": train.cfg.moe.n_experts,
                  "layers": train.cfg.n_layers,
                  "calibrate": callable(job.calibrate)}))
''' % CELL


def _digests(root):
    out = {}
    for d, _, names in os.walk(root):
        for n in names:
            p = os.path.join(d, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


def test_a_new_architecture_enters_by_files_alone(tmp_path):
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "*.pyc"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench_json = json.load(f)
    before = _digests(chip)

    # the new files, and new entries in BENCHMARK.json
    _write_json(chip / "configs" / "stub-moe.json", CONFIG)
    (chip / "reference_stub_moe.py").write_text(STUB)
    with open(chip / "traffic" / "fsdp4-train-s1024.json") as f:
        traffic = json.load(f)
    traffic.update(strategy="fsdp_bf16", seq_len=32, global_batch=2)
    _write_json(chip / "traffic" / "stub-train.json", traffic)
    shutil.copy(chip / "limits" / "qwen3-0.6b.train-s1024.json",
                chip / "limits" / (CELL + ".json"))
    new = json.loads(json.dumps(bench_json))
    new["configs"].append({
        "name": "stub-moe", "source": CONFIG["source"],
        "file": "benchmarks/chip/configs/stub-moe.json",
        "reduced": CONFIG["reduced"], "why": "a stub"})
    new["workloads"].append({
        "name": CELL, "config": "stub-moe", "traffic": "stub-train",
        "chips": 1, "why": "a stub"})
    for m in new["per_layer"]:
        if m["name"] == "train_mfu":
            m["workloads"].append(CELL)
    _write_json(root / "BENCHMARK.json", new)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root / "benchmarks"),
                                           str(root / "src")]))
    p = subprocess.run([sys.executable, "-c", DRIVE], cwd=root, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == {"reference": str(chip / "reference_stub_moe.py"),
                   "model": "chip.reference_stub_moe", "experts": 8,
                   "layers": 3, "calibrate": True}

    # the copy's own file checks, the config-follows-source check among
    # them, accept the new files as they stand
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", str(chip / "test_bench_files.py"),
         "-k", "stub or format or end_to_end"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-4000:]
    assert "5 passed" in p.stdout, p.stdout[-2000:]

    # no file the benchmark had was changed
    after = _digests(chip)
    assert {k: after[k] for k in before} == before


def test_no_module_takes_a_cell_reference_but_by_its_name():
    """Only the configuration's ``"reference"`` leads to a reference
    module: no harness file imports ``chip.reference`` itself."""
    found = []
    for d, _, names in os.walk(HERE):
        for n in names:
            if not n.endswith(".py"):
                continue
            path = os.path.join(d, n)
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.ImportFrom):
                    mods = [f"{node.module}.{a.name}" for a in node.names]
                    mods.append(node.module or "")
                elif isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                else:
                    continue
                if "chip.reference" in mods:
                    found.append(os.path.relpath(path, HERE))
    assert found == []
