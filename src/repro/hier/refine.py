"""Segmented refinement: re-place fine nodes window by window.

After the GDP policy places the coarse graph and :meth:`Coarsening.
expand` lifts that placement to fine nodes, this pass streams over the
fine graph one topological window at a time and lets the policy
re-decide the window's nodes with everything *outside* the window held
fixed:

* the window's :class:`~repro.core.featurize.GraphBatch` comes from
  :func:`~repro.core.featurize.featurize_window` (out-of-core — peak RSS
  is bounded by the window, not the graph);
* the current assignment enters the decode as the *incumbent* via the
  migration-bias path (``policy.sample(..., incumbent=, migration_bias=)``),
  so the policy proposes moves rather than re-placing from scratch;
* per-device memory caps are reduced by the bytes outside the window
  already resident on each device, so no candidate can overflow a device
  regardless of what the rest of the graph does;
* every candidate is scored on the FULL-graph simulator and accepted
  only if strictly better *and* valid.

Accept-only-if-better makes the refined makespan monotonically ≤ the
coarse-only makespan, and the cap reduction makes cap-safety structural
— both pinned by tests/test_hier.py.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Union

import jax
import numpy as np

from repro.core import policy
from repro.core.featurize import featurize, featurize_window
from repro.core.graph import DataflowGraph
from repro.graphs.shards import GraphShards


@dataclasses.dataclass
class RefineResult:
    """Outcome of one refinement sweep."""
    placement: np.ndarray          # i32[N] final fine placement
    makespan: float                # full-graph makespan of `placement`
    trajectory: List[float]        # makespan after each window (index 0 =
    #                                the incoming coarse-level makespan)
    accepted: int                  # windows whose proposal was taken
    windows: int                   # windows visited
    wall_s: float                  # sweep wall time


def _window_batch(source: Union[DataflowGraph, GraphShards], lo: int,
                  hi: int, topo, pad_to: int, scale):
    if isinstance(source, GraphShards):
        return featurize_window(source, lo, hi, topo=topo, pad_to=pad_to,
                                scale=scale)
    # in-RAM fallback (small graphs / tests): featurize the whole graph
    # once would defeat the point at scale, but windows of an in-RAM
    # graph still go through the shard-free slow path for parity tests.
    from repro.graphs.shards import write_shards
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        return featurize_window(write_shards(source, d), lo, hi, topo=topo,
                                pad_to=pad_to, scale=scale)


def refine(params, cfg, env, source: Union[DataflowGraph, GraphShards],
           topo, current: np.ndarray, *, key,
           window: int = 8192, num_samples: int = 4,
           migration_bias: float = 2.0, temperature: float = 1.0,
           max_windows: Optional[int] = None,
           log_every: int = 0) -> RefineResult:
    """One streaming refinement sweep over ``source``.

    ``env`` must be a full-graph :class:`~repro.sim.scheduler.Env` (its
    arrays are O(N) scalars — the same budget the coarsener uses);
    ``current`` is the incoming fine placement (typically
    ``coarsening.expand(coarse_placement)``).  Windows are uniform
    ``[i·window, (i+1)·window)`` ranges, all padded to ``window`` so the
    whole sweep reuses ONE compiled decode program.
    """
    n = source.num_nodes
    d = topo.num_devices
    current = np.asarray(current, np.int32).copy()
    mem = (source.column("mem_bytes") if isinstance(source, GraphShards)
           else source.mem_bytes).astype(np.float64)
    caps = topo.mem_caps.astype(np.float64)
    alive = caps[caps > 0]
    tight = alive.min() if alive.size else 1.0

    mk, _, valid = env.rewards(current[None])
    best_mk = float(mk[0])
    t0 = time.perf_counter()
    traj = [best_mk]
    accepted = 0
    num_windows = (n + window - 1) // window
    if max_windows is not None:
        num_windows = min(num_windows, max_windows)

    usage = np.bincount(current, weights=mem, minlength=d)[:d]
    for i in range(num_windows):
        lo, hi = i * window, min((i + 1) * window, n)
        gb = _window_batch(source, lo, hi, topo, window, cfg.scale)
        win_usage = np.bincount(current[lo:hi], weights=mem[lo:hi],
                                minlength=d)[:d]
        outside = usage - win_usage
        cap_adj = (np.maximum(caps - outside, 0.0) / tight).astype(np.float32)
        gb = gb._replace(dev_mem_cap=np.asarray(cap_adj))

        key, k = jax.random.split(key)
        samples, _ = policy.sample(params, cfg, gb, d, k, num_samples,
                                   temperature=temperature,
                                   incumbent=current[lo:hi],
                                   migration_bias=migration_bias)
        samples = np.asarray(samples)[:, :hi - lo]

        cands = np.tile(current, (num_samples, 1))
        cands[:, lo:hi] = samples
        mks, _, valids = env.rewards(cands)
        mks = np.where(np.asarray(valids), np.asarray(mks), np.inf)
        j = int(mks.argmin())
        if mks[j] < best_mk:
            current = cands[j]
            best_mk = float(mks[j])
            usage = np.bincount(current, weights=mem, minlength=d)[:d]
            accepted += 1
        traj.append(best_mk)
        if log_every and (i == 0 or (i + 1) % log_every == 0):
            print(f"[refine] window {i + 1}/{num_windows} "
                  f"best={best_mk:.4f}s accepted={accepted}")

    return RefineResult(placement=current, makespan=best_mk,
                        trajectory=traj, accepted=accepted,
                        windows=num_windows,
                        wall_s=time.perf_counter() - t0)
