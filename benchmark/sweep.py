"""``python -m benchmark.sweep --workload <cell> --rates 1,1.5,2
--lead 20 --seconds 30``: find a serving cell's knee, once, on the chip.

One replica, one warm-up, then at each rate in turn (same seed, same
distributions) ``--lead`` seconds of load that are not judged, so that
the window opens on a loaded engine, and a window of ``--seconds``. A
line of JSON for each: the tails over the requests due in the window
and how many of them had no token when it closed. The knee is the highest rate at which the tails stay
flat and none is left waiting; the traffic file gets its rate from it,
as a number. Not part of a run: no bound rests on what this prints.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from . import driver, loadgen
from . import run as bench_run
from .jobs import serve
from .readers import serve as readers


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--lead", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)
    cell, config, traffic = bench_run.load_cell(
        bench_run.load_benchmark(), args.workload)
    if "rate_per_s" not in traffic:
        raise SystemExit(
            f"traffic {cell['traffic']!r} offers no rate to sweep: it holds "
            f"{traffic.get('concurrency')} streams open (a closed loop), and "
            f"a closed loop has no knee to find")
    try:
        driver.require_chips(cell["chips"])
    except driver.NoChip as e:
        print(f"benchmark.sweep: {e}", file=sys.stderr)
        return bench_run.NO_CHIP_EXIT
    rates = [float(r) for r in args.rates.split(",")]
    schedules = [loadgen.schedule({**traffic, "rate_per_s": rate}, args.seed,
                                  args.lead + args.seconds,
                                  config["vocab_size"])
                 for rate in rates]
    with serve.deployed(cell, config, traffic, args.seed) as (port, call):
        serve.warm_up(port, [r for s in schedules for r in s],
                      config["engine"])
        for rate, requests in zip(rates, schedules):
            # The engine cannot cancel: what the last window left
            # behind must finish before the next one starts.
            while call("facts")["engine"]["active_slots"]:
                time.sleep(1.0)
            load = loadgen.run_open_loop("127.0.0.1", port, "/llm/stream",
                                         requests, traffic["clients"],
                                         args.lead + args.seconds,
                                         traffic["grace_s"])
            judged = [s for s in load["samples"] if s["due_s"] >= args.lead]
            record = {"client": {**load, "samples": judged}}
            print(json.dumps({
                "rate_per_s": rate, "requests": len(judged),
                "failed": sum(s["error"] is not None for s in judged),
                "ttft_p50_s": readers.ttft_p50_s(record),
                "ttft_p90_s": readers.ttft_p90_s(record),
                "gap_p50_s": readers.gap_p50_s(record),
                "gap_p90_s": readers.gap_p90_s(record),
                "no_token_yet": sum(not s["token_s"] for s in judged),
                "cut": sum(s["cut"] for s in judged),
                "late_s_max": readers.loadgen_late_s_max(record),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
