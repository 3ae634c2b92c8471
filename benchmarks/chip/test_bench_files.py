"""Every cell, configuration, traffic and metric named in BENCHMARK.json
resolves to files that load, and the entries keep to their format."""
import json
import os
import re

import pytest

from chip import bench
from chip.conftest import HERE, ROOT, load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_reports_the_end_to_end_metrics():
    b = _bench()
    for w in b["workloads"]:
        names = {m["name"] for m in b["end_to_end"]
                 if bench.applies(m, w["name"])}
        assert {"setup_s", "train_tokens_per_s"} <= names


def test_benchmark_entries_keep_their_format():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in b[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({e["name"] for e in b[k]}) == len(b[k])
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0 < m["bound"] <= 0.25 for m in b["end_to_end"])
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert any(w["name"] in m.get("workloads", cells)
                   for m in b["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_cell_files_resolve(cell):
    b = _bench()
    files = bench.cell_files(b, cell)
    assert os.path.exists(files["job"])
    from chip.reference_base import for_config
    for_config(files["config"])
    from repro.configs import get_config
    get_config(files["config"]["registry"])
    lim = files["limits"]["limits"]
    assert set(lim) == {"loss_gap", "grad_norm_gap", "update_norm_gap"}
    for m in b["per_layer"]:
        if bench.applies(m, cell, {"train_tokens_per_s", "setup_s"}):
            assert os.path.exists(os.path.join(HERE, "metrics",
                                               m["name"] + ".py"))


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(HERE, "configs"))))
def test_config_files_follow_their_source(name):
    """Published sizes unchanged unless listed in ``reduced``, and a
    program configuration built from them by the configuration's own
    reference module: every size the program reads back equals the file's;
    each key in ``reduced`` states its published value beside the cut,
    and a cut file states the deployment it stands for."""
    from chip.reference_base import for_config
    cfg = load("configs", name + ".json")
    model = for_config(cfg)
    prog, _ = model.program_config(cfg)
    values = model.program_values(prog)
    assert {"num_hidden_layers", "hidden_size"} <= set(values)
    for key, value in values.items():
        if key in cfg:
            assert value == cfg[key], (key, value, cfg[key])
    published = cfg.get("published", {})
    for key in cfg["reduced"]:
        assert key in cfg and key in published, key
        assert published[key] != cfg[key], key
    assert set(published) <= set(cfg["reduced"])
    if cfg["reduced"]:
        assert cfg.get("deployment")
    entries = {c["name"]: c for c in _bench()["configs"]}
    if name not in entries:       # a configuration no cell runs yet
        return
    entry = entries[name]
    assert entry["file"] == f"benchmarks/chip/configs/{name}.json"
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["source"] == entry["source"]


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(HERE, "traffic"))))
def test_traffic_files_name_a_job(name):
    t = load("traffic", name + ".json")
    assert os.path.exists(os.path.join(HERE, "jobs", t["job"] + ".py"))
    assert t["check_steps"] >= 3 and t["schedule"]["warmup"] > \
        t["check_steps"]
