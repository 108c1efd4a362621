#!/usr/bin/env python3
"""One-chip smoke run of GDP's placement path on a TPU.

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process at a
time), in four phases:

0. device — fail unless JAX's first device is a TPU (there is no CPU
   fallback); print the jax / jaxlib / libtpu versions, the device kind
   and the persistent compile-cache directory.
1. main path — ``repro.api.place(method="finetune")`` on the 53,909-node
   8-layer GNMT (the paper's headline scale) over an 8-device P100 fleet
   with the large-graph campaign's policy, PPO and scale configs, from
   seeded random weights.  Checks: no new jit programs in steady
   iterations; the plan's on-chip makespan equals the numpy reference
   simulator's; autoregressive (AR) sampling and the teacher-forced (TF)
   pass agree on the per-node log-probs of the same placements.
2. kernel path — the same graph and weights with the Pallas band-attention
   and CSR max-pool kernels: both must compile to Mosaic custom calls,
   the TF log-probs must match phase 1's jnp path, and one PPO update
   must run through the kernels' custom VJPs.
3. serving — a ``PlacementService`` on the wall clock answers 8 requests
   (5 Table-1 graphs, then 3 repeats) with phase 1's trainer.

A failed check raises, so the exit code is non-zero and no result line is
printed.  On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

NUM_DEVICES = 8
FINETUNE_ITERS = 3
SAMPLES = 4
# on-chip f32 simulator vs the float64 numpy reference.  Near a 3.7 s
# clock one f32 ulp is 2.4e-7 s and most GNMT-8 ops last 6.15e-6 s, so
# every such add rounds the same way: the f32 makespan drifts ~3e-4 high
# at 53,909 nodes, identically on the CPU backend.  1e-4 cannot hold.
MAKESPAN_RTOL = 1e-3
AR_TF_ATOL = 1e-2         # per-node logp: keeps PPO ratios within 1% of 1
KERNEL_ATOL = 1e-3        # per-node logp: kernel path vs jnp path


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


class IterLog:
    """``PPOTrainer.run_log`` sink: stamps each PPO iteration once the
    updated parameters are on the device (``block_until_ready``)."""

    def __init__(self, trainer):
        self.trainer = trainer
        self.records, self.stamps = [], []

    def emit(self, rec) -> None:
        jax.block_until_ready(self.trainer.state.params)
        self.stamps.append(time.perf_counter())
        self.records.append(rec)


# ------------------------------------------------------------------ phase 0
def phase0_device():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX's first device is "
                         f"platform={dev.platform!r} ({dev.device_kind})")
    from importlib import metadata
    from repro.obs.jaxprof import enable_compile_cache
    cache_dir = enable_compile_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"phase 0: jax {jax.__version__} jaxlib "
        f"{metadata.version('jaxlib')} libtpu {libtpu}; "
        f"device {dev.platform}/{dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache_dir}")
    return dev


# ------------------------------------------------------------------ phase 1
def phase1_main_path(g):
    from benchmarks.large_graph import (LARGE_SCALE, SLACK, large_policy,
                                        large_ppo)
    from repro.api import Budget, place
    from repro.core import baselines as B
    from repro.core import policy as P
    from repro.core.featurize import featurize
    from repro.core.ppo import PPOTrainer
    from repro.sim import p100_topology, prepare_sim_graph
    from repro.sim.reference import simulate_ref
    from repro.sim.scheduler import Env, SimConfig

    topo = p100_topology(NUM_DEVICES).with_mem_caps(
        g.total_mem() / NUM_DEVICES * SLACK)
    pcfg, ppo = large_policy(), large_ppo(SAMPLES)
    tr = PPOTrainer(pcfg, ppo, seed=0)
    it_log = IterLog(tr)
    tr.run_log = it_log
    t0 = time.perf_counter()
    plan = place(g, topo, method="finetune", pcfg=pcfg, ppo=ppo, trainer=tr,
                 budget=Budget(finetune_iters=FINETUNE_ITERS,
                               samples=SAMPLES))
    t_place = time.perf_counter() - t0
    stamps, recs = it_log.stamps, it_log.records
    check(len(recs) == FINETUNE_ITERS, f"{len(recs)} PPO iterations ran")
    setup_s = stamps[0] - t0
    steady = np.diff(stamps)
    new_programs = sum(r["retraces"] for r in recs[1:])
    steady_compiles = sum(r["compiles"] for r in recs[1:])
    log(f"phase 1: {g.num_nodes} nodes; place() {t_place:.3f} s; set-up + "
        f"compile + iteration 1 {setup_s:.3f} s; steady s/iteration "
        f"{[float(s) for s in steady]} (mean {float(steady.mean())!r}); "
        f"in iterations 2..{FINETUNE_ITERS}: new jit programs "
        f"{new_programs}, backend compiles {steady_compiles}")
    check(new_programs == 0, f"{new_programs} jit programs compiled in "
                             f"steady iterations")
    check(steady_compiles == 0, "backend compiles in steady iterations")

    # the plan against round-robin, judged by the same segmented env
    gb = featurize(g, topo=topo, scale=LARGE_SCALE.with_segment_padding())
    pad_n = gb.op.shape[0]
    sg = prepare_sim_graph(g, topo, pad_to=pad_n,
                           pad_multiple=LARGE_SCALE.segment)
    env = Env.from_config(sg, topo, SimConfig(), segment=LARGE_SCALE.segment)

    def on_chip(pl):
        padded = np.zeros(pad_n, np.int32)
        padded[:g.num_nodes] = pl
        mk, _, ok = env.rewards(padded[None])
        return float(np.asarray(mk)[0]), bool(np.asarray(ok)[0])

    rr = np.asarray(B.round_robin(g, topo), np.int32)
    log(f"phase 1: plan.valid={plan.valid} plan.makespan={plan.makespan!r} "
        f"vs round_robin {on_chip(rr)[0]!r}")
    check(plan.valid, "plan is invalid")
    check(math.isfinite(plan.makespan), "plan makespan is not finite")
    for name, pl in (("plan", plan.placement), ("round_robin", rr)):
        mk, ok = on_chip(pl)
        ref_mk, _, ref_ok = simulate_ref(g, pl, topo)
        rel = abs(mk - ref_mk) / ref_mk
        log(f"phase 1: {name} on-chip makespan {mk!r} (valid={ok}) vs numpy "
            f"reference {ref_mk!r} (valid={ref_ok}): rel {rel:.3e} (bound "
            f"{MAKESPAN_RTOL})")
        check(ok == ref_ok, f"{name}: validity disagrees with the reference")
        check(rel <= MAKESPAN_RTOL, f"{name}: makespan rel error {rel:.3e}")

    # AR sampling vs teacher-forced log-probs of the same placements
    params = tr.state.params
    pl, lp_ar = P.sample(params, pcfg, gb, NUM_DEVICES,
                         jax.random.PRNGKey(7), SAMPLES)
    lp_tf, _ = P.logp_and_entropy(params, pcfg, gb, NUM_DEVICES, pl)
    ar_tf = float(jnp.abs(lp_ar - lp_tf).max())
    log(f"phase 1: max |logp_AR - logp_TF| over {SAMPLES} x {g.num_nodes} "
        f"nodes = {ar_tf:.3e} (bound {AR_TF_ATOL}); peak_bytes_in_use "
        f"{peak_bytes()}")
    check(ar_tf <= AR_TF_ATOL, f"AR/TF logp gap {ar_tf:.3e}")
    return dict(tr=tr, topo=topo, gb=gb, sg=sg, pl=pl, lp_tf=lp_tf)


# ------------------------------------------------------------------ phase 2
def phase2_kernels(g, p1):
    from benchmarks.large_graph import LARGE_SCALE, large_ppo
    from repro.core import gnn, placer as PL, policy as P
    from repro.core.featurize import featurize
    from repro.core.ppo import PPOTrainer, clone_state
    from repro.kernels.ops import interpret
    from repro.sim.scheduler import Env, SimConfig

    tr = p1["tr"]
    sc = dataclasses.replace(LARGE_SCALE, csr=True)
    pcfg = dataclasses.replace(tr.pcfg, scale=sc, attn_impl="pallas_band",
                               agg_impl="pallas_csr")
    gb = featurize(g, topo=p1["topo"], scale=sc.with_segment_padding())
    params = tr.state.params

    # the GNN and one TF segment, compiled alone: the kernels must be
    # Mosaic custom calls, not interpreted
    t0 = time.perf_counter()
    gnn_hlo = jax.jit(lambda p, b: gnn.apply(
        p, b, agg_impl="pallas_csr", scale=sc)).lower(
        params["gnn"], gb).compile().as_text()
    s, hid, dmax = pcfg.scale.segment, pcfg.hidden, pcfg.max_devices
    hd = hid // pcfg.heads
    mem = jnp.zeros((pcfg.placer_layers, pcfg.window - 1, pcfg.heads, hd))
    tf_hlo = PL._tf_segment.lower(
        params["placer"], jnp.zeros((s, hid)), mem, mem, jnp.ones((s,)),
        jnp.int32(0), jnp.ones((hid,)), jnp.zeros((dmax, hid)),
        jnp.zeros((s, dmax)), jnp.zeros((s,)), jnp.ones((dmax,)), None,
        heads=pcfg.heads, num_devices=NUM_DEVICES, use_attention=True,
        attn_impl="pallas_band").compile().as_text()
    calls = {"gnn": gnn_hlo.count("tpu_custom_call"),
             "tf_segment": tf_hlo.count("tpu_custom_call")}
    log(f"phase 2: tpu_custom_call count per compiled program {calls} "
        f"({time.perf_counter() - t0:.3f} s to compile both)")
    check(interpret() or all(calls.values()),
          f"kernels not compiled for TPU: {calls}")

    lp_k, _ = P.logp_and_entropy(params, pcfg, gb, NUM_DEVICES, p1["pl"])
    gap = float(jnp.abs(lp_k - p1["lp_tf"]).max())
    log(f"phase 2: max |logp_kernels - logp_jnp| = {gap:.3e} "
        f"(bound {KERNEL_ATOL})")
    check(gap <= KERNEL_ATOL, f"kernel/jnp logp gap {gap:.3e}")

    env = Env.from_config(p1["sg"], p1["topo"],
                          SimConfig(shaped_reward=True),
                          segment=LARGE_SCALE.segment)
    trk = PPOTrainer(pcfg, large_ppo(SAMPLES), seed=1,
                     state=clone_state(tr.state))
    t1 = time.perf_counter()
    rec = trk.iteration(g.name, gb, env, NUM_DEVICES)
    jax.block_until_ready(trk.state.params)
    log(f"phase 2: one PPO iteration through both kernels "
        f"{time.perf_counter() - t1:.3f} s (compile included); loss "
        f"{rec['loss']!r} best makespan {rec['best_makespan']!r}; "
        f"peak_bytes_in_use {peak_bytes()}")
    check(math.isfinite(rec["loss"]) and math.isfinite(rec["best_makespan"]),
          "kernel-path PPO iteration produced non-finite values")


# ------------------------------------------------------------------ phase 3
def phase3_serving(tr):
    from benchmarks import common as C
    from repro.serve import PlacementService, ServeConfig, WallClock
    from repro.sim import p100_topology

    graphs = [t.graph for t in C.paper_tasks()
              if t.name in ("rnnlm-2", "gnmt-2", "transformer_xl-2",
                            "inception", "wavenet-2")]
    topo = p100_topology(4).with_mem_caps(
        max(g.total_mem() for g in graphs) * 1.2)
    svc = PlacementService(tr, ServeConfig(
        max_batch=4, max_wait_s=0.0, num_samples=SAMPLES,
        escalate_margin=math.inf), clock=WallClock())
    order = graphs + [graphs[0], graphs[2], graphs[1]]
    reqs = []
    for g in order:
        reqs.append(svc.submit(g, topo))
        svc.step(force=True)
    svc.drain()
    for r in reqs:
        log(f"phase 3: req{r.req_id} {r.graph.name:>20s} "
            f"({r.graph.num_nodes} nodes) source={r.source:<9s} "
            f"latency {r.latency:.4f} s makespan {r.makespan!r}")
    check(all(r.done_t is not None for r in reqs), "unanswered requests")
    check(all(math.isfinite(r.makespan) for r in reqs),
          "non-finite served makespan")
    check(all(r.source == "cache" for r in reqs[len(graphs):]),
          "repeated requests missed the cache")


def main() -> None:
    dev = phase0_device()
    from repro.graphs import synthetic as S
    from repro.obs import jaxprof
    compiles0 = jaxprof.backend_compiles()
    t0 = time.perf_counter()
    g = S.gnmt(8, time_steps=352)
    check(g.num_nodes >= 50_000, f"GNMT-8 has {g.num_nodes} nodes")
    p1 = phase1_main_path(g)
    t1 = time.perf_counter()
    phase2_kernels(g, p1)
    t2 = time.perf_counter()
    phase3_serving(p1["tr"])
    t3 = time.perf_counter()
    log(f"phase seconds: main {t1 - t0:.3f}, kernels {t2 - t1:.3f}, "
        f"serving {t3 - t2:.3f}; backend compiles "
        f"{jaxprof.backend_compiles() - compiles0}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
