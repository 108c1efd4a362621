#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python chipbench/calibrate.py --workload <name> --seeds <n> [<n> ...] \
        [--control <k>] [--seconds <s>]

For each seed it reads the numbers a run compares, the program against
the plain reference (the lower readings).  For the first ``--control``
seeds it also reads the control: the reference with float8 inputs to
its matrix products put in the program's place (the upper readings); for a training cell, also
the fault of half the batch left out (the reference on half the samples).
A training cell needs no window; a serving cell runs a window of
``--seconds`` at the mix's rate per seed.  One JSON line per reading.
Everything runs in this one process; the benchmark's runs never run it.
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


def leaf_detail(prog, refr, w0, names):
    """The names of the worst kept leaves, the median kept leaf's gaps,
    the leaves the thousandth rule left out, and every leaf's norms (first
    gradient and change, this side's and the reference's)."""
    import numpy as np
    from chipbench.drivers import ppo
    keep = ppo.kept_leaves(refr)
    g = ppo.leaf_gaps(prog["grad1"], refr["grad1"], keep)
    dp = [a - b for a, b in zip(prog["params"], w0)]
    dr = [a - b for a, b in zip(refr["params"], w0)]
    c = ppo.leaf_gaps(dp, dr, keep)
    kept = [n for n, k in zip(names, keep) if k]

    def norms(leaves):
        return [float(np.linalg.norm(a)) for a in leaves]

    return {"grad1_median_leaf_gap": float(np.median(g)),
            "change_median_leaf_gap": float(np.median(c)),
            "grad1_worst_leaf": kept[int(np.argmax(g))],
            "change_worst_leaf": kept[int(np.argmax(c))],
            "left_out_leaves": [n for n, k in zip(names, keep) if not k],
            "names": list(names),
            "norms": {"grad1": norms(prog["grad1"]),
                      "grad1_ref": norms(refr["grad1"]),
                      "change": norms(dp), "change_ref": norms(dr)}}


def ppo_readings(cfg, seed: int, control: bool):
    import jax
    from chipbench.drivers import ppo
    objs, w0, prog, steps = ppo.checked_steps(cfg, seed)
    caps, g = objs[1], objs[0]
    del objs
    flat = jax.tree_util.tree_flatten_with_path(w0)[0]
    names = [jax.tree_util.keystr(k) for k, _ in flat]
    leaves = [v for _, v in flat]
    lim = cfg["correct"]["limits"]
    ref32 = ppo.reference_steps(g, caps, cfg, w0, steps)
    out = [("program", ppo.compare(prog, ref32, leaves, lim),
            leaf_detail(prog, ref32, leaves, names))]
    if control:
        ref8 = ppo.reference_steps(g, caps, cfg, w0, steps, "float8")
        out.append(("control", ppo.compare(ref8, ref32, leaves, lim),
                    leaf_detail(ref8, ref32, leaves, names)))
        half = [pl[:pl.shape[0] // 2] for pl in steps]
        refh = ppo.reference_steps(g, caps, cfg, w0, half)
        out.append(("half_batch", ppo.compare(refh, ref32, leaves, lim),
                    leaf_detail(refh, ref32, leaves, names)))
    return out


def serve_readings(cell, control: bool):
    from chipbench.drivers import serve
    out = serve.run(dict(cell, control=control))
    res = [("program", out["checks"], {})]
    if control:
        res.append(("control", [harness.Check(k, v, float("nan")) for k, v
                                in out["control"].items()], {}))
    return res


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args()
    bench = harness.benchmark()
    wl = harness.workload(bench, args.workload)
    dev = harness.require_chips(int(wl["chips"]))
    import jax
    harness.enable_compile_cache()
    cfg = harness.config_file(bench, wl["config"])
    mix = harness.traffic_file(wl["traffic"])
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        control = i < args.control
        if mix["driver"] == "ppo":
            res = ppo_readings(cfg, seed, control)
        else:
            cell = {"name": args.workload, "config": cfg, "traffic": mix,
                    "seed": seed, "seconds": args.seconds, "trace": False,
                    "t_start": t, "devices": jax.devices()[:1],
                    "layers": {}, "peak_flops": None,
                    "metrics": [m["name"] for m in
                                harness.end_to_end(bench, wl)]}
            res = serve_readings(cell, control)
        for kind, checks, detail in res:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, "device": dev.device_kind,
                              "seconds": time.perf_counter() - t,
                              "readings": {c.name: c.value for c in checks},
                              "detail": detail}), flush=True)


if __name__ == "__main__":
    main()
