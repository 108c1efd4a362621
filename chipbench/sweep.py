#!/usr/bin/env python3
"""Find the highest request rate a serving cell sustains: run its window
at each of several fixed open-loop rates, in one process on the chip.

    python chipbench/sweep.py --workload <name> --rates 2 4 8 \
        [--seconds 15] [--seed 1]

Prints one JSON line per rate: the rate offered, the cell's latency
percentiles from the due time, placements per second answered in the
window, and failures.  The cell's mix then
fixes its rate at about four fifths of the highest rate at which the
answers keep up (placements per second near the rate offered, and the
tail not growing with the window).  The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    bench = harness.benchmark()
    wl = harness.workload(bench, args.workload)
    dev = harness.require_chips(int(wl["chips"]))
    import jax
    harness.enable_compile_cache()
    cfg = harness.config_file(bench, wl["config"])
    mix = harness.traffic_file(wl["traffic"])
    driver = harness.load_module("drivers", mix["driver"])
    names = [m["name"] for m in harness.end_to_end(bench, wl)]
    for rate in args.rates:
        t = time.perf_counter()
        out = driver.run({"name": args.workload, "config": cfg,
                          "traffic": dict(mix, rate_rps=rate),
                          "seed": args.seed, "seconds": args.seconds,
                          "trace": False, "t_start": t,
                          "devices": jax.devices()[:1], "layers": {},
                          "peak_flops": None, "metrics": names})
        print(json.dumps({"workload": args.workload, "rate_rps": rate,
                          "device": dev.device_kind,
                          "metrics": out["metrics"],
                          "placements_per_s":
                              out["answered_in_window"] / args.seconds,
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "correct": all(c.ok for c in out["checks"])}),
              flush=True)


if __name__ == "__main__":
    main()
