"""``python -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of BENCHMARK.json.

Finds the cell's configuration and traffic files by name, hands them to
the job kind the traffic file names (``benchmark/jobs/<kind>.py``),
reads each of the cell's metrics through the one reader that the
metric's own file names (``benchmark/metrics/<metric>.json``:
``{"reader": "benchmark.readers.serve.gap_p90_s"}``), and prints the contract's JSON object as the last line
of standard output. This process never imports jax.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from typing import Callable, Dict, List, Mapping

from . import driver, trace_reduce
from .driver import CHECKOUT

HERE = os.path.dirname(os.path.abspath(__file__))
NO_CHIP_EXIT = 3


def load_benchmark() -> Dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: Mapping, name: str):
    """(cell, configuration file's dict, traffic file's dict)."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(CHECKOUT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def find_reader(metric: str) -> Callable:
    """Exactly the function that ``benchmark/metrics/<metric>.json``
    names by its dotted path; a file added elsewhere cannot change what
    an old metric reads."""
    path = os.path.join(HERE, "metrics", metric + ".json")
    if not os.path.exists(path):
        raise SystemExit(f"metric {metric!r} has no file {path}")
    with open(path) as f:
        module, _, function = json.load(f)["reader"].rpartition(".")
    return getattr(importlib.import_module(module), function)


def cell_metrics(bench: Mapping, cell: str, group: str) -> List[Dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def read_metrics(metrics: List[Mapping], record: Mapping) -> Dict:
    out = {}
    for metric in metrics:
        value = find_reader(metric["name"])(record)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    process_start = time.time()
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench = load_benchmark()
    cell, config, traffic = load_cell(bench, args.workload)
    try:
        driver.require_chips(cell["chips"])
    except driver.NoChip as e:
        print(f"benchmark.run: {e}", file=sys.stderr)
        return NO_CHIP_EXIT

    job = importlib.import_module(f"benchmark.jobs.{traffic['kind']}")
    record = job.run(cell, config, traffic, args.seed, args.seconds,
                     bool(args.trace))
    record.update(cell=cell, config=config, traffic=traffic,
                  seconds=args.seconds, process_start=process_start)

    group = "per_layer" if args.trace else "end_to_end"
    worker, trace = record["worker"], record["trace"]
    device = {**worker["device"],
              "memory_peak_bytes": worker["memory_peak_bytes"]}
    result = {
        "correct": record["correct"],
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": read_metrics(cell_metrics(bench, cell["name"], group),
                                record),
        "device": device,
        "programs_in_window": worker["programs_in_window"],
        # Seconds from this process's start to each phase of set-up.
        "setup": {k: t - process_start
                  for k, t in worker["phases"].items()},
        "compile": {k: worker[k] for k in (
            "cache_hits", "cache_misses", "programs", "programs_loaded_s")},
    }
    if not args.trace:
        # Beside the contract's metrics: the cell's per-layer numbers
        # that need no trace, for whoever reads the run by hand.
        result["also"] = {k: v["value"] for k, v in read_metrics(
            [m for m in cell_metrics(bench, cell["name"], "per_layer")
             if m["source"] != "device_trace"], record).items()}
    if args.trace:
        if not trace:
            raise SystemExit("--trace 1 produced no trace")
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = trace_reduce.breakdown(trace)
        # What jax.profiler.stop_trace() took in the worker (a serve
        # job's; it grows with the steps in the traced window).
        result["stop_trace_s"] = worker.get("stop_trace_s")
    # Each number compared beside its limit: last in the line, and the
    # last line of standard error.
    result["check"] = worker["check"]
    print("check: " + json.dumps(worker["check"]), file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
