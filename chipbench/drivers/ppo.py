"""Driver: PPO fine-tuning of the GDP policy on one graph.

Set-up builds the graph, featurizes it, makes the weights from the seed
and builds one trainer, then drives that trainer through its first steps
with the window's own call (``PPOTrainer.iteration``); the first step
compiles.  The window then runs whole iterations until ``seconds`` have
passed, each ended by ``block_until_ready`` on the updated parameters.

``correct`` compares the first steps with the plain reference, which
follows them from the same weights on the same sampled placements: the
simulator's makespans and validity, the per-node log-probs of the first
step's placements that the update was given, the first gradient as the
optimizer took it (Adam's first moment after one step over ``1 - b1``),
and each leaf's change after the steps.
"""
from __future__ import annotations

import gc
import time
from functools import partial
from typing import Any, Dict, List

import numpy as np

from chipbench import flops, harness, trace
from chipbench.drivers import common
from chipbench.reference import features, policy as ref, sim as ref_sim


class RecordingEnv:
    """The training environment, passed to the trainer in place of the
    program's own: it forwards each reward call and, while ``recording``,
    keeps the placements it was given and what the simulator returned.
    ``before_next`` runs once before the next call (the traced iteration
    starts its second capture there)."""

    def __init__(self, env):
        self.env = env
        self.recording = True
        self.calls: List[tuple] = []
        self.before_next = None

    def rewards(self, placements):
        if self.before_next is not None:
            self.before_next()
            self.before_next = None
        out = self.env.rewards(placements)
        if self.recording:
            self.calls.append((placements, *out))
        return out


def leaf_gaps(prog: List[np.ndarray], refv: List[np.ndarray],
              keep: np.ndarray) -> np.ndarray:
    """Per kept leaf, |norm(program) - norm(reference)| over the larger of
    the reference's norm of that leaf and of the median leaf."""
    pn = np.array([np.linalg.norm(a) for a in prog])
    rn = np.array([np.linalg.norm(a) for a in refv])
    med = np.median(rn[keep])
    return (np.abs(pn - rn) / np.maximum(rn, med))[keep]


def kept_leaves(refr: Dict[str, Any]) -> np.ndarray:
    """Leaves whose first gradient in the reference is at least a
    thousandth of the median leaf's: the rest are nought up to rounding
    (a key's bias under softmax; the device keys on a fleet of identical
    devices, which the reference's head cancels exactly) and move under
    Adam by round-off alone."""
    rg = np.array([np.linalg.norm(a) for a in refr["grad1"]])
    return rg >= 1e-3 * np.median(rg)


def build(cfg: Dict[str, Any], seed: int):
    """(graph, fleet caps, GraphBatch, env, trainer, weights) for one run."""
    import jax
    from repro.core.featurize import featurize
    from repro.sim.scheduler import Env, SimConfig, prepare_sim_graph
    from chipbench import weights

    w_seed, t_seed = harness.sub_seeds(seed, 2)
    g = common.graph(cfg["graph"])
    caps = common.mem_caps(cfg["fleet"], g)
    topo = common.topology(cfg["fleet"], caps)
    pcfg = common.policy_config(cfg["policy"])
    ppo = common.ppo_config(cfg["ppo"])
    sc = pcfg.scale
    gb = featurize(g, topo=topo, scale=sc.with_segment_padding())
    sg = prepare_sim_graph(g, topo, pad_to=gb.op.shape[0],
                           pad_multiple=sc.segment)
    env = RecordingEnv(Env.from_config(sg, topo, SimConfig(shaped_reward=True),
                                       segment=sc.segment))
    params = weights.make(w_seed, cfg["policy"])
    jax.block_until_ready(params)
    tr = common.trainer(pcfg, ppo, t_seed, params)
    return g, caps, gb, env, tr, params


def reference_steps(g, caps, cfg, w0, steps, precision="float32"):
    """The reference's makespans, first gradient and parameters after
    following ``steps`` (placements [M, N] of the real nodes per step);
    ``precision="float8"`` is the control."""
    import jax
    import jax.numpy as jnp
    fl = features.fleet_arrays(common.reference_fleet(cfg["fleet"], caps))
    inp = {k: jnp.asarray(v) for k, v in
           features.policy_inputs(g, fl).items()}
    si = features.sim_inputs(g, fl)
    pcfg, pp = cfg["policy"], cfg["ppo"]
    step_fn = jax.jit(partial(ref.ppo_step, policy=pcfg, ppo=pp,
                              num_devices=fl["num_devices"],
                              precision=precision))
    logp_fn = jax.jit(lambda p, x: ref.logp_entropy(
        p, inp, x, policy=pcfg, num_devices=fl["num_devices"],
        precision=precision)[0])
    params, adam = w0, ref.adam_zeros(w0)
    out = {"makespan": [], "valid": [], "grad1": None}
    baseline = {"count": 0, "value": 0.0}
    for k, pl in enumerate(steps):
        mk, util, valid = ref_sim.simulate(si, pl)
        adv = ref.advantages(ref_sim.shaped_reward(mk, util), baseline,
                             pp["adv_norm"])
        coef = pp["entropy_coef"] * pp["entropy_decay"] ** k
        with jax.default_matmul_precision("highest"):
            if k == 0:
                out["logp"] = np.asarray(logp_fn(
                    params, jnp.asarray(pl, jnp.int32)))
            _, grad, params, adam = step_fn(
                params, adam, inp, jnp.asarray(pl, jnp.int32),
                jnp.asarray(adv), jnp.float32(coef))
        out["makespan"].append(mk)
        out["valid"].append(valid)
        if k == 0:
            out["grad1"] = [np.asarray(a) for a in
                            jax.tree_util.tree_leaves(grad)]
    out["params"] = [np.asarray(a) for a in jax.tree_util.tree_leaves(params)]
    return out


def compare(prog: Dict[str, Any], refr: Dict[str, Any], w0_leaves,
            limits: Dict[str, float]) -> List[harness.Check]:
    """The numbers ``correct`` compares, each with its limit.  The
    log-prob gap is the widest over every node of every sample of the
    first step.  The gradient and the change are taken at the worst kept
    leaf, so a fault in a few leaves shows.  Each step's loss is not
    compared: it is dominated by the entropy term and reads alike (under
    2.1e-4) for the program, the control and a batch missing half its
    samples."""
    mk_rel = max(float(np.max(np.abs(p - r[:len(p)]) / r[:len(p)],
                              initial=0.0))
                 for p, r in zip(prog["makespan"], refr["makespan"]))
    valid_gap = sum(int(np.sum(p != r[:len(p)])) for p, r in
                    zip(prog["valid"], refr["valid"]))
    keep = kept_leaves(refr)
    grad = leaf_gaps(prog["grad1"], refr["grad1"], keep)
    dp = [a - b for a, b in zip(prog["params"], w0_leaves)]
    dr = [a - b for a, b in zip(refr["params"], w0_leaves)]
    change = leaf_gaps(dp, dr, keep)
    lp = prog["logp"]
    logp_gap = float(np.max(np.abs(lp - refr["logp"][:len(lp)])))
    return [harness.Check("makespan_rel", mk_rel, limits["makespan_rel"]),
            harness.Check("valid_mismatch", valid_gap, 0),
            harness.Check("logp_gap", logp_gap, limits["logp_gap"]),
            harness.Check("grad1_worst_leaf_gap", np.max(grad),
                          limits["grad1_worst_leaf_gap"]),
            harness.Check("change_worst_leaf_gap", np.max(change),
                          limits["change_worst_leaf_gap"])]


def traced_iteration(tr, g, gb, env, d) -> List[trace.Capture]:
    """One iteration under the profiler, in two captures: sampling, then
    simulation and update.  At 53,909 nodes one capture of the whole
    iteration overflows the profiler's buffers (every step of the decode
    and simulator scans is an op event) and drops the update."""
    import jax
    first, second = trace.Capture(), trace.Capture()

    def switch():
        # the device runs programs in order: once this one is done, so is
        # the sampling phase's last program
        harness.device_sync()
        first.__exit__(None, None, None)
        second.__enter__()

    first.__enter__()
    env.before_next = switch
    with jax.profiler.TraceAnnotation("chipbench.iteration"):
        tr.iteration(g.name, gb, env, d)
        jax.block_until_ready(tr.state.params)
    second.__exit__(None, None, None)
    return [first, second]


def checked_steps(cfg: Dict[str, Any], seed: int):
    """Set-up: build the run's objects and drive the trainer through the
    checked steps with the window's own call.  Returns the objects, the
    weights (host), the program's readings and the placements per step."""
    import jax
    from repro.core import ppo as program
    g, caps, gb, env, tr, w0 = build(cfg, seed)
    d = int(cfg["fleet"]["num_devices"])
    w0 = jax.tree_util.tree_map(np.asarray, w0)
    prog = {"grad1": None}
    update, given = program._update_any, []

    def tap(params, opt_state, pcfg, ocfg, gb_, nd, placements, old_logp,
            *rest):
        # the log-probs the iteration computed for its placements
        given.append(old_logp)
        return update(params, opt_state, pcfg, ocfg, gb_, nd, placements,
                      old_logp, *rest)

    program._update_any = tap
    try:
        for k in range(int(cfg["correct"]["steps"])):
            tr.iteration(g.name, gb, env, d)
            jax.block_until_ready(tr.state.params)
            if k == 0:
                b1 = 0.9
                prog["grad1"] = [
                    np.asarray(a) / (1 - b1) for a in
                    jax.tree_util.tree_leaves(tr.state.opt_state.mu)]
    finally:
        program._update_any = update
    env.recording = False
    prog["params"] = [np.asarray(a) for a in
                      jax.tree_util.tree_leaves(tr.state.params)]
    n = g.num_nodes
    prog["logp"] = np.asarray(given[0])[:, :n]
    steps = [np.asarray(c[0])[:, :n] for c in env.calls]
    prog["makespan"] = [np.asarray(c[1]) for c in env.calls]
    prog["valid"] = [np.asarray(c[3]) for c in env.calls]
    return (g, caps, gb, env, tr), w0, prog, steps


def run(cell: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    from repro.obs.trace import Tracer, set_tracer

    cfg, seconds = cell["config"], cell["seconds"]
    counter = harness.CompileCounter()
    (g, caps, gb, env, tr), w0, prog, steps = checked_steps(cfg, cell["seed"])
    w0_leaves = jax.tree_util.tree_leaves(w0)
    d, n = int(cfg["fleet"]["num_devices"]), g.num_nodes
    harness.device_sync()
    setup_s = time.perf_counter() - cell["t_start"]
    harness.log(f"set-up {setup_s:.3f} s ({len(steps)} checked steps, "
                f"{counter.n} backend compiles); {n} nodes")

    tracer = Tracer(enabled=bool(cell["trace"]))
    old = set_tracer(tracer)
    compiles0, iters, traced = counter.n, 0, []
    t0 = time.perf_counter()
    try:
        while True:
            if cell["trace"] and iters == 0:
                traced = traced_iteration(tr, g, gb, env, d)
            else:
                with jax.profiler.TraceAnnotation("chipbench.iteration"):
                    tr.iteration(g.name, gb, env, d)
                    jax.block_until_ready(tr.state.params)
            iters += 1
            if time.perf_counter() - t0 >= seconds:
                break
    finally:
        set_tracer(old)
    window_s = time.perf_counter() - t0
    compiles = counter.n - compiles0
    harness.log(f"window {window_s:.3f} s, {iters} iterations, "
                f"{compiles} backend compiles")
    peak = harness.peak_bytes(cell["devices"])

    del tr, gb, env
    gc.collect()
    t_ref = time.perf_counter()
    refr = reference_steps(g, caps, cfg, w0, steps)
    checks = compare(prog, refr, w0_leaves, cfg["correct"]["limits"])
    harness.log(f"reference {time.perf_counter() - t_ref:.3f} s")

    out = {"metrics": {"setup_s": setup_s, "ppo_iter_s": window_s / iters},
           "attempted": iters, "failed": 0, "checks": checks,
           "memory_peak_bytes": peak}
    if traced:
        spans = [(s.ts, s.ts + s.dur, s.name) for s in tracer.spans]
        red = trace.merge([trace.reduce(c.raw, cell["layers"], spans,
                                        c.clock_start) for c in traced])
        n_samples = int(cfg["ppo"]["num_samples"])
        out["layer_inputs"] = {
            "trace": red, "iterations": 1, "compiles_in_window": compiles,
            "flops": flops.ppo_iteration(n, cfg["policy"], n_samples,
                                         int(cfg["ppo"]["epochs"])),
            "peak_flops": cell["peak_flops"]}
    return out

