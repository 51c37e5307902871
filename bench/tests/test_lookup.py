"""Every name in BENCHMARK.json resolves to its files, and a new
configuration, traffic mix or metric is found from new files alone."""
import json
import shutil

import pytest

import lookup


@pytest.fixture(scope="module")
def bench():
    return lookup.load_benchmark()


def test_every_workload_resolves(bench):
    for w in bench["workloads"]:
        cell = lookup.cell(bench, w["name"])
        cfg = cell["config"]
        for key in ("generator", "rows", "dim", "data_seed", "query_noise", "index", "limits"):
            assert key in cfg, (w["name"], key)
        assert callable(lookup.generator(cfg["generator"]))
        for key in ("batch", "k", "mode"):
            assert key in cell["traffic"]
        assert cell["end_to_end"] and cell["per_layer"]


def test_every_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        assert callable(lookup.metric_reader(m["name"]))


def test_config_files_match_their_entries(bench):
    for c in bench["configs"]:
        cfg = json.loads((lookup.REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


@pytest.mark.parametrize("kind", ["workload", "traffic", "metric", "generator"])
def test_unknown_names_are_errors(bench, kind):
    with pytest.raises(LookupError):
        if kind == "workload":
            lookup.cell(bench, "no-such-cell")
        elif kind == "traffic":
            lookup.traffic("no-such-mix")
        elif kind == "metric":
            lookup.metric_reader("no.such_metric")
        else:
            lookup.generator("no_such_generator")


def test_new_cell_from_new_files_only(bench, tmp_path, monkeypatch):
    repo = tmp_path / "repo"
    shutil.copytree(lookup.BENCH, repo / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    monkeypatch.setattr(lookup, "BENCH", repo / "bench")
    (repo / "bench/traffic/read-b8-k1.json").write_text(
        json.dumps({"batch": 8, "k": 1, "mode": "all"}))
    (repo / "bench/metrics/executor.steps_total.py").write_text(
        "def read(r):\n    return sum(b.steps for b in r.batches)\n")
    cfg = json.loads((lookup.REPO / bench["configs"][0]["file"]).read_text())
    cfg["name"] = "db1-small"
    (repo / "bench/configs/db1-small.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(bench))
    bench["configs"].append(dict(bench["configs"][0], name="db1-small",
                                 file="bench/configs/db1-small.json"))
    bench["workloads"].append({"name": "db1-small-k1", "config": "db1-small",
                               "traffic": "read-b8-k1", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "executor.steps_total", "unit": "steps",
                               "better": "lower", "source": "program_counter",
                               "layer": "executor", "moves": "query_throughput",
                               "workloads": ["db1-small-k1"]})
    cell = lookup.cell(bench, "db1-small-k1", repo=repo)
    assert cell["traffic"]["batch"] == 8
    assert cell["config"]["name"] == "db1-small"
    assert "executor.steps_total" in [m["name"] for m in cell["per_layer"]]
    assert lookup.metric_reader("executor.steps_total")(
        type("R", (), {"batches": [type("B", (), {"steps": 3})()] * 2})()) == 6
    other = lookup.cell(bench, bench["workloads"][0]["name"], repo=repo)
    assert "executor.steps_total" not in [m["name"] for m in other["per_layer"]]
