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

from benchmark import arch  # noqa: E402
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


REFERENCE_OFFERS = ("loss", "logit_margins", "LOSS_ATOL", "LOGIT_MARGIN_TOL")
COUNTS_OFFER = ("train_flops_per_token", "flash_train_flops",
                "flash_train_bytes", "kv_bytes_per_token", "decode_step_flops",
                "decode_step_bytes", "param_counts", "head_dim")


def _config_file(entry):
    with open(os.path.join(REPO, entry["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_names_its_architecture_under_paths(entry):
    config = _config_file(entry)
    assert set(config["arch"]) == set(arch.ROLES)
    roots = tuple(os.path.join(REPO, p) + os.sep for p in BENCH["paths"])
    reference, counts = arch.reference(config), arch.counts(config)
    assert reference.__file__.startswith(roots)
    assert counts.__file__.startswith(roots)
    assert reference.__name__ == config["arch"]["reference"]
    assert counts.__name__ == config["arch"]["counts"]
    assert all(callable(getattr(reference, n)) for n in REFERENCE_OFFERS[:2])
    assert all(config["dtype"] in getattr(reference, n)
               for n in REFERENCE_OFFERS[2:])
    assert all(callable(getattr(counts, n)) for n in COUNTS_OFFER)
    # The program's config comes from the function the file names, and
    # carries the file's sizes.
    cfg = arch.program_config(config)
    assert (cfg.hidden_size, cfg.num_layers, cfg.dh) == (
        config["hidden_size"], config["num_hidden_layers"],
        counts.head_dim(config))
    module, _, function = config["arch"]["program_config"].rpartition(".")
    assert sys.modules[module].__file__.startswith(roots)
    assert callable(getattr(sys.modules[module], function))


@pytest.mark.parametrize("change,says", [
    (lambda c: c.pop("arch"), "no arch.counts"),
    (lambda c: c.update(arch=None), "no arch.counts"),
    (lambda c: c["arch"].pop("counts"), "no arch.counts"),
    (lambda c: c["arch"].update(counts=["benchmark.flops"]), "no arch.counts"),
    (lambda c: c["arch"].update(counts="ray_tpu.models.llama"), "outside"),
    (lambda c: c["arch"].update(counts="json"), "outside"),
    (lambda c: c["arch"].update(counts="tests.conftest"), "outside"),
    (lambda c: c["arch"].update(counts="benchmark...ray_tpu.models.llama"),
     "outside"),
], ids=["no-arch", "arch-null", "no-role", "not-a-name", "program-module",
        "stdlib", "beside-paths", "dots-leading-out"])
def test_a_config_without_arch_or_naming_outside_paths_is_an_error(change, says):
    config = _config_file(BENCH["configs"][0])
    assert arch.counts(config).__name__ == "benchmark.flops"
    change(config)
    with pytest.raises(ValueError, match=says):
        arch.counts(config)


def test_a_name_under_paths_with_no_file_is_an_import_error():
    config = _config_file(BENCH["configs"][0])
    config["arch"]["reference"] = "benchmark.no_such_reference"
    with pytest.raises(ImportError, match="no_such_reference"):
        arch.reference(config)
    config["arch"]["program_config"] = "llama_config"  # no module named
    with pytest.raises(ValueError, match="outside"):
        arch.program_config(config)


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
        # An open loop states its rate, a closed loop its streams.
        assert ("rate_per_s" in traffic) != ("concurrency" in traffic)
        if "concurrency" in traffic:
            assert 1 <= traffic["concurrency"] <= traffic["clients"]
            assert traffic["concurrency"] <= config["engine"]["max_batch"]
            assert traffic["requests"] >= 4 * traffic["concurrency"]
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
