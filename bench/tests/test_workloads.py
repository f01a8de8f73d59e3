"""Every cell of BENCHMARK.json is found by name: its workload file, its
configuration, its driver and its per-layer readers."""

import json
import pathlib

import pytest

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_with_config_and_driver(name):
    entry = harness.benchmark_entry(name)
    workload = harness.load_workload(name)
    assert workload["chips"] == entry["cell"]["chips"]
    assert workload["config"] == entry["cell"]["config"]
    config = harness.load_config(workload["config"])
    assert config["name"] == workload["config"]
    driver = harness.load_driver(workload["driver"])
    assert callable(driver.run)
    assert set(workload["limits"]) and all(
        v >= 0 for v in workload["limits"].values())
    names = [m["name"] for m in entry["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert entry["per_layer"]
    for metric in entry["per_layer"]:
        assert callable(harness.load_reader(metric["name"]))


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_holds_what_benchmark_json_says(entry):
    path = harness.ROOT / entry["file"]
    assert path == harness.BENCH / "configs" / f"{entry['name']}.json"
    config = json.loads(path.read_text())
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    for key in ("deployment", "precision", "assumed"):
        assert config[key]
    sizes = config.get("model", {})
    for key in entry["reduced"]:
        assert key in sizes


def test_every_metric_has_a_reader_and_moves_an_end_to_end_metric():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for metric in SPEC["per_layer"]:
        assert metric["moves"] in e2e
        assert (harness.BENCH / "metrics" / f"{metric['name']}.py").exists()


def test_reader_finding_nothing_returns_nothing():
    ctx = {"trace": {"window_s": 1.0, "busy_s": 0.5, "modules_s": {},
                     "collective_s": 0.0, "collective_exposed_s": 0.0},
           "scopes": {}, "steps": 10, "window_s": 1.0, "counts": {},
           "chips": 1, "peaks": harness.load_peaks("TPU v5 lite")}
    for name in ("mfu.train", "chunk_us_per_step.paper",
                 "record_us_per_step.paper",
                 "collective_us_per_step.paper",
                 "collective_exposed_us_per_step.paper",
                 "grad_us_per_step.train", "opt_us_per_step.train",
                 "plan_us_per_step.paper", "dispatch_us_per_step.paper"):
        assert harness.load_reader(name)(ctx) is None
