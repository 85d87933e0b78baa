"""BENCHMARK.json against the files it names: every name resolves, every
``moves`` target is reported by the same cells, names and units use only
the allowed characters. CPU, no processes, no sleeps."""

import importlib
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run as bench_run  # noqa: E402

BENCH = bench_run.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _cells_of(metric):
    return set(metric.get("workloads", CELLS))


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])
    four = [c for c in BENCH["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_is_the_source_with_only_depth_cut(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and len(entry["why"]) <= 200
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    # No width may be cut: the reduced keys are depth alone.
    assert entry["reduced"] == ["num_hidden_layers"]
    assert any(c["config"] == entry["name"] for c in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_to_config_traffic_and_job(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    _, config, traffic = bench_run.load_cell(BENCH, cell["name"])
    job = importlib.import_module(f"benchmark.jobs.{traffic['kind']}")
    assert callable(job.run)
    if traffic["kind"] == "train":
        assert config["trainer"] and traffic["batch"] % traffic["check_sequences"] == 0
    else:
        assert isinstance(traffic["rate_per_s"], (int, float))
        assert traffic["prompt"]["max"] + traffic["output"]["max"] \
            <= config["engine"]["max_len"]
    reported = {g: {m["name"] for m in bench_run.cell_metrics(BENCH, cell["name"], g)}
                for g in ("end_to_end", "per_layer")}
    assert "setup_s" in reported["end_to_end"]
    assert len(reported["end_to_end"]) >= 2 and reported["per_layer"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_has_a_reader_and_allowed_names(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    end_to_end = metric in BENCH["end_to_end"]
    allowed |= {"bound"} if end_to_end else {"layer", "moves"}
    assert set(metric) <= allowed and set(metric) >= allowed - {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    reader = bench_run.find_reader(metric["name"])
    assert callable(reader)
    # The metric's own file names its reader by dotted path, and that
    # is the function defined there, not one that merely shares a name.
    with open(os.path.join(REPO, "benchmark", "metrics",
                           metric["name"] + ".json")) as f:
        dotted = json.load(f)["reader"]
    assert dotted == f"{reader.__module__}.{reader.__name__}"
    assert dotted.startswith("benchmark.readers.")
    assert _cells_of(metric) <= set(CELLS)
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        target, = [m for m in BENCH["end_to_end"]
                   if m["name"] == metric["moves"]]
        assert _cells_of(metric) <= _cells_of(target)
        assert 1 <= len(metric["layer"]) <= 200
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_a_metric_without_its_file_is_an_error():
    with pytest.raises(SystemExit, match="no_such_metric.chat"):
        bench_run.find_reader("no_such_metric.chat")


def test_every_metric_file_belongs_to_a_metric():
    files = {f[:-5] for f in os.listdir(
        os.path.join(REPO, "benchmark", "metrics"))}
    assert files == {m["name"] for m in METRICS}
