#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is the ``workloads`` entry of ``BENCHMARK.json`` named by
``--workload``; its configuration and traffic mix are files found by
name, and the mix names the driver that runs it.  The run fails, printing
no result, unless JAX sees a TPU with as many chips as the cell asks for.
It builds the cell, warms up every shape it will use, measures for
``--seconds`` and checks what the timed path produced against the plain
reference.  With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of a short steady part of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``: each compared number with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402


def run_cell(name: str, seed: int, seconds: float, traced: bool,
             require_chip: bool = True, config=None, mix=None, peaks=None):
    """Run one cell; returns (result dict, checks).  ``config``, ``mix``
    and ``peaks`` replace the configuration file, the traffic file and the
    table of peaks (tests run a cell at a small size on the CPU, with
    ``require_chip`` off)."""
    import jax
    bench = harness.benchmark()
    wl = harness.workload(bench, name)
    if require_chip:
        harness.require_chips(int(wl["chips"]))
    dev = jax.devices()[0]
    cache = harness.enable_compile_cache() if require_chip else "off"
    cfg = config or harness.config_file(bench, wl["config"])
    mix = mix or harness.traffic_file(wl["traffic"])
    peaks = peaks or harness.load_json(
        os.path.join(harness.BENCH_DIR, "peaks.json"))
    if traced and dev.device_kind not in peaks["devices"]:
        raise SystemExit(f"chipbench: no peaks for device kind "
                         f"{dev.device_kind!r} in chipbench/peaks.json")
    harness.log(f"{name} seed {seed} seconds {seconds} trace {int(traced)} "
                f"on {dev.platform}/{dev.device_kind} x{len(jax.devices())}; "
                f"compile cache {cache}")
    devices = jax.devices()[:int(wl["chips"])]
    reported = [m["name"] for m in harness.end_to_end(bench, wl)]
    cell = {"name": name, "config": cfg, "traffic": mix, "seed": seed,
            "seconds": seconds, "trace": traced, "t_start": T_START,
            "devices": devices, "metrics": reported,
            "layers": harness.load_json(
                os.path.join(harness.BENCH_DIR, "layers.json"))["layers"],
            "peak_flops": (peaks["devices"][dev.device_kind]["bf16_flops"]
                           if traced else None)}
    out = harness.load_module("drivers", mix["driver"]).run(cell)
    checks = out["checks"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": all(c.ok for c in checks),
              "attempted": out["attempted"], "failed": out["failed"]}
    if traced:
        red = out["layer_inputs"]["trace"]
        metrics = harness.per_layer(bench, wl, reported, out["layer_inputs"])
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]})
    else:
        units = {m["name"]: m["unit"] for m in harness.end_to_end(bench, wl)}
        result.update(metrics={k: {"value": float(out["metrics"][k]),
                                   "unit": u} for k, u in units.items()},
                      device=device)
    return result, checks


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    result, checks = run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace))
    harness.emit(result, checks)


if __name__ == "__main__":
    main()
