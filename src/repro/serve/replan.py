"""Migration-aware re-placement after a fleet change.

When devices fail or links degrade, the incumbent placement is not just
invalid — it is *information*: every surviving node's state already
lives somewhere, and a replan that gratuitously shuffles nodes pays for
each move in checkpoint-restore / peer-copy bytes (``ckpt.elastic`` is
the consumer that actually reshards the state).  This module turns the
policy into a migration-aware replanner:

1. **repair** — keep every surviving assignment, greedily re-place only
   the nodes whose device died (cheapest possible migration, makespan
   takes what it gets);
2. **incumbent-biased samples** — the AR decode conditioned on the
   incumbent placement (``core.policy.sample(..., incumbent=...,
   migration_bias=...)``): the policy trades makespan against moved
   bytes node-by-node;
3. **from-scratch samples** — the unconditioned decode, the paper's
   zero-shot path and the baseline every chaos benchmark compares
   against.

Selection is **band-constrained lexicographic**: among all valid
candidates whose makespan is within ``(1 + makespan_slack)`` of the best
valid from-scratch makespan, pick the one moving the fewest bytes
(ties: lower makespan).  The best scratch candidate is itself in-band,
so whenever scratch can recover at all the winner (a) never moves more
bytes than from-scratch replanning and (b) is within the slack on
recovery makespan — the two properties ``benchmarks/chaos.py`` reports
as its headline and ``tests/test_chaos.py`` pins.

Everything is deterministic: one seed draws all samples, candidates are
evaluated through the jitted scheduler in a single batch, and the same
(graph, fleet, incumbent, failure) inputs replay bit-identically.
"""
from __future__ import annotations

import dataclasses
import time
from typing import FrozenSet, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import policy
from repro.core.featurize import featurize
from repro.core.graph import DataflowGraph
from repro.core.policy import PolicyConfig
from repro.sim.chaos import alive_devices, migration_bytes
from repro.sim.device import Topology
from repro.sim.scheduler import Env, SimConfig, prepare_sim_graph


@dataclasses.dataclass(frozen=True)
class ReplanConfig:
    """Knobs of the migration-aware replanner."""
    num_samples: int = 8          # per pool: biased AND scratch draws
    temperature: float = 0.5      # near-greedy serving-style decode
    makespan_slack: float = 0.05  # band over the best scratch makespan
    migration_bias: float = 4.0   # stay-put logit strength (x mem_frac)
    seed: int = 0
    # from-scratch baseline mode: ignore the incumbent when CHOOSING
    # (candidate pool = the scratch draws only, winner = best valid
    # makespan) while still reporting moved bytes against it.  The
    # scratch pool uses the same key derivation as the aware mode's
    # internal scratch draws, so the aware winner is guaranteed to move
    # no more bytes than this baseline AND land within the slack of its
    # makespan — the chaos headline, exact by construction.
    scratch_only: bool = False


@dataclasses.dataclass(frozen=True)
class ReplanResult:
    """One replan decision plus the from-scratch comparison the chaos
    benchmark reports against."""
    placement: np.ndarray         # i32[N] the selected recovery placement
    makespan: float
    valid: bool
    moved_bytes: float            # by-choice migration volume (see
    forced_bytes: float           # sim.chaos.migration_bytes)
    source: str                   # "repair" | "biased" | "scratch"
    latency_s: float              # wall-clock of the whole replan
    num_candidates: int
    scratch_makespan: float       # best valid from-scratch candidate
    scratch_moved_bytes: float    # ... and the bytes it would move


def repair_placement(g: DataflowGraph, topo: Topology,
                     incumbent: np.ndarray,
                     failed: Sequence[int] = ()) -> np.ndarray:
    """Minimal-migration repair: survivors stay put, dead nodes go to the
    alive device with the most remaining memory (greedy, topo order).

    Moves zero by-choice bytes by construction; makespan is whatever the
    greedy packing yields.  Falls back to device 0 if nothing is alive
    (the caller's validity check will reject it).
    """
    inc = np.asarray(incumbent, np.int64)
    assert inc.shape == (g.num_nodes,), inc.shape
    alive = [int(d) for d in alive_devices(topo)]
    dead = set(int(d) for d in failed)
    dead.update(d for d in range(topo.num_devices) if d not in alive)
    out = inc.copy()
    if not alive:
        out[:] = 0
        return out.astype(np.int32)
    caps = topo.mem_caps.astype(np.float64)
    load = np.zeros(topo.num_devices)
    on_dead = np.isin(inc, list(dead)) if dead else np.zeros(len(inc), bool)
    surv = ~on_dead
    np.add.at(load, inc[surv], g.mem_bytes[surv])
    for i in np.flatnonzero(on_dead):
        free = caps[alive] - load[alive]
        d = alive[int(np.argmax(free))]
        out[i] = d
        load[d] += g.mem_bytes[i]
    return out.astype(np.int32)


def replan(params, cfg: PolicyConfig, g: DataflowGraph, topo: Topology,
           incumbent: np.ndarray, failed: Sequence[int] = (),
           sim: SimConfig = SimConfig(),
           rcfg: ReplanConfig = ReplanConfig()) -> ReplanResult:
    """Choose a recovery placement for ``g`` on the (possibly degraded)
    fleet ``topo``, given where state currently lives.

    Candidate pool = repair + incumbent-biased samples + from-scratch
    samples, all evaluated through the jitted scheduler in one batch;
    winner = band-constrained lexicographic (moved_bytes, makespan) —
    see the module docstring for the guarantee this buys.
    """
    t0 = time.perf_counter()
    n = g.num_nodes
    dead = frozenset(int(d) for d in failed)
    inc = np.asarray(incumbent, np.int32)

    # decode must not emit dead devices: force the memory-aware mask on
    # (dev_mem_cap is 0 for failed devices, so they are closed).
    pcfg = dataclasses.replace(cfg, mask_full_devices=True)
    seg = cfg.scale.segment
    gb = featurize(g, topo=topo,
                   scale=dataclasses.replace(cfg.scale, pad_multiple=seg))

    # nodes whose device died must be restored anyway (forced bytes) —
    # they carry no stay-put preference.
    inc_eff = inc.copy()
    if dead:
        inc_eff[np.isin(inc, list(dead))] = -1

    key = jax.random.PRNGKey(rcfg.seed)
    kb, ks = jax.random.split(key)
    d = topo.num_devices
    pad_n = gb.op.shape[0]
    scratch, _ = policy.sample(params, pcfg, gb, d, ks, rcfg.num_samples,
                               temperature=rcfg.temperature)
    if rcfg.scratch_only:
        cand = np.asarray(scratch, np.int32)[:, :pad_n].copy()
        sources = ["scratch"] * rcfg.num_samples
    else:
        biased, _ = policy.sample(params, pcfg, gb, d, kb,
                                  rcfg.num_samples,
                                  temperature=rcfg.temperature,
                                  incumbent=inc_eff,
                                  migration_bias=rcfg.migration_bias)
        repair = repair_placement(g, topo, inc, dead)
        cand = np.zeros((1 + 2 * rcfg.num_samples, pad_n), np.int32)
        cand[0, :n] = repair
        cand[1:1 + rcfg.num_samples] = np.asarray(
            biased, np.int32)[:, :pad_n]
        cand[1 + rcfg.num_samples:] = np.asarray(
            scratch, np.int32)[:, :pad_n]
        sources = (["repair"] + ["biased"] * rcfg.num_samples
                   + ["scratch"] * rcfg.num_samples)
    cand[:, n:] = 0      # padding nodes: device 0, zero cost

    sg = prepare_sim_graph(g, topo, pad_multiple=seg)
    assert sg.compute_t.shape[0] == pad_n, (sg.compute_t.shape, pad_n)
    env = Env.from_config(sg, topo, sim, segment=seg)
    mks, _, valid = env.rewards(cand)
    mks = np.asarray(mks, np.float64)
    valid = np.asarray(valid, bool)
    moved = np.zeros(len(cand))
    forced = np.zeros(len(cand))
    for i in range(len(cand)):
        moved[i], forced[i] = migration_bytes(g, inc, cand[i, :n], dead)

    # band anchor: the best VALID from-scratch candidate; if scratch never
    # recovers, anchor on the best valid candidate of any source.
    sc = np.array([s == "scratch" for s in sources])
    if (valid & sc).any():
        anchor = mks[valid & sc].min()
        si = int(np.flatnonzero(valid & sc)[np.argmin(mks[valid & sc])])
    elif valid.any():
        anchor = mks[valid].min()
        si = int(np.flatnonzero(valid)[np.argmin(mks[valid])])
    else:   # nothing fits (fleet too small): report the least-bad plan
        i = int(np.argmin(mks))
        return ReplanResult(cand[i, :n].copy(), float(mks[i]), False,
                            float(moved[i]), float(forced[i]), sources[i],
                            time.perf_counter() - t0, len(cand),
                            float(mks[i]), float(moved[i]))
    if rcfg.scratch_only:        # baseline: best valid makespan, period
        w = si
    else:
        band = (1.0 + rcfg.makespan_slack) * anchor
        in_band = valid & (mks <= band)
        order = sorted(np.flatnonzero(in_band),
                       key=lambda i: (moved[i], mks[i]))
        w = int(order[0])
    return ReplanResult(cand[w, :n].copy(), float(mks[w]), True,
                        float(moved[w]), float(forced[w]), sources[w],
                        time.perf_counter() - t0, len(cand),
                        float(mks[si]), float(moved[si]))


def make_replace_fn(params, cfg: PolicyConfig,
                    sim: SimConfig = SimConfig(),
                    rcfg: ReplanConfig = ReplanConfig()):
    """Adapter to :func:`sim.chaos.recovery_trajectory`'s ``replace_fn``
    signature (g, topo, incumbent, failed) -> placement."""
    def fn(g: DataflowGraph, topo: Topology, incumbent: np.ndarray,
           failed: FrozenSet[int]) -> np.ndarray:
        return replan(params, cfg, g, topo, incumbent, failed,
                      sim=sim, rcfg=rcfg).placement
    return fn


def make_scratch_fn(params, cfg: PolicyConfig,
                    sim: SimConfig = SimConfig(),
                    rcfg: ReplanConfig = ReplanConfig()):
    """From-scratch baseline: same scratch draws (same key derivation),
    winner = best valid makespan — migration cost never considered."""
    rc = dataclasses.replace(rcfg, scratch_only=True)

    def fn(g: DataflowGraph, topo: Topology, incumbent: np.ndarray,
           failed: FrozenSet[int]) -> np.ndarray:
        return replan(params, cfg, g, topo, incumbent, failed,
                      sim=sim, rcfg=rc).placement
    return fn
