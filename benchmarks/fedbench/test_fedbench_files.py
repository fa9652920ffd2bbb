"""Every configuration, cell and metric file parses and agrees with
``BENCHMARK.json``; a cell is found by name alone."""
import json
import re

import pytest

from fedbench import gen, spec
from repro.core.api import TASKS

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/fedbench"]
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert (spec.CHECKOUT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert entry["file"] == f"benchmarks/fedbench/configs/{name}.json"
    cfg = spec.config(name)
    assert cfg["name"] == name and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert hasattr(spec.model(name), "forward_macs")
    assert any(w["config"] == name for w in BENCH["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_file(name):
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    raw = spec.load_json(spec.HERE / "workloads" / f"{entry['traffic']}.json")
    assert raw["config"] == entry["config"] and raw["why"] == entry["why"]
    assert raw["chips"] == entry["chips"] == 1
    cell = spec.workload(name)
    gen.traffic(cell["traffic"])
    assert cell["window"]["round_s_hint"] > 0
    assert cell["window"]["check_rounds"] >= 1
    assert cell["limits"], "a cell without limits is never judged"
    assert spec.config(cell["config"])["task"] in TASKS


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_reader(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    assert NAME.match(metric)
    assert callable(spec.reader(metric))
    assert m["better"] in ("lower", "higher")
    for cell in m.get("workloads", []):
        assert cell in CELLS


def test_names_and_bounds():
    for entry in BENCH["configs"] + BENCH["workloads"] + METRICS:
        assert NAME.match(entry["name"]), entry["name"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    for cell in CELLS:
        assert len(spec.metrics_of(BENCH, cell, "end_to_end")) >= 2
        assert spec.metrics_of(BENCH, cell, "per_layer")


def test_a_cell_outside_the_tree_loads_by_path(tmp_path):
    """A later cell is a new file: nothing in the harness names it."""
    cell = spec.workload("cnn_fedbwo_paper")
    cell.update(name="new_cell", traffic=dict(cell["traffic"], n_clients=5))
    path = tmp_path / "new_cell.json"
    path.write_text(json.dumps(cell))
    got = spec.workload(str(path))
    assert got["name"] == "new_cell" and got["traffic"]["n_clients"] == 5


def test_same_shapes_for_every_seed():
    """The seed draws data, never sizes: one program per cell."""
    t = gen.Traffic(n_train=2000, n_test=100, n_clients=20, batch_size=10,
                    partition="dirichlet", partition_seed=3)
    a, b = gen.make_dataset(t, 1), gen.make_dataset(t, 2**33 + 7)
    assert a.n_batches == b.n_batches
    assert a.server_seed != b.server_seed
    assert not (a.clients[0]["images"] == b.clients[0]["images"]).all()
