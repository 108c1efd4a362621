"""Jittable list scheduler: (graph, placement) -> step time, memory, reward.

This is the RL environment.  Nodes are visited in topological order inside a
``lax.fori_loop``; each node's ready time is the max over its (padded)
in-edges of producer finish time plus a cross-device transfer cost, and each
device executes its ops in arrival order (``dev_free``).

Heterogeneity is native: compute times are a per-(node, device) matrix
(mixed device generations run the same op at different speeds), transfers
are charged through ``[D, D]`` bandwidth/latency matrices gathered per
edge endpoint pair, and memory validity is per-device (each device has its
own capacity).  A uniform :class:`~repro.sim.device.Topology` collapses to
the historical homogeneous semantics bit-for-bit (pinned by
``tests/test_hetero.py``).  Per-device memory is the sum of resident bytes
of the ops placed there; exceeding any device's capacity makes the
placement invalid (paper: reward −10).

A pure-numpy reference with identical semantics lives in
``repro/sim/reference.py`` and anchors the property tests.
"""
from __future__ import annotations

import dataclasses
from functools import cached_property, partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import DataflowGraph
from repro.obs import jaxprof
from repro.sim.cost_model import node_compute_matrix
from repro.sim.device import Topology

INVALID_REWARD = -10.0


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """First-class simulator semantics knobs, threaded through every layer.

    One value of this config describes *how* makespans are produced — the
    training envs, the serving ladder, the baselines, and the benchmarks
    all evaluate placements under the same ``SimConfig`` so a number from
    one layer is comparable to a number from any other.

    * ``sender_contention`` — serialize each device's outgoing transfers
      on a single send port (see :func:`simulate`).  This is a *semantic
      mode*: makespans under contention are not comparable to makespans
      without it, so the serving tier folds the mode into its topology
      digest (``serve.fingerprint.topology_fingerprint``) and the
      persistent store invalidates cross-mode records at load, exactly
      like a policy bump.
    * ``receiver_contention`` — the mirror mode: serialize each device's
      *incoming* transfers on a single receive port.  Composes freely
      with ``sender_contention`` (both ports must be free before a
      transfer starts).
    * ``jittered_bandwidth`` — deterministic per-edge bandwidth jitter:
      every cross-device transfer's duration is multiplied by a factor in
      ``[1, 1 + jitter_amp]`` drawn from an integer hash of
      ``(src, dst, src_dev, dst_dev, jitter_seed)``.  Same seed ⇒ same
      makespans, bit-for-bit, on every path (monolithic, segmented, and
      the numpy oracle reproduce the same factors).
    * ``shaped_reward`` — continuous memory penalty instead of the
      paper's −10 cliff (:func:`reward_shaped`); training envs use it,
      evaluation envs do not.

    All communication modes are provenance: they feed the topology
    fingerprint and the store's ``mode_bits``, so flipping any of them
    invalidates cached/persisted placements exactly like a policy bump.

    The default config is bit-identical to the historical semantics —
    every golden-pinned makespan is a ``SimConfig()`` makespan.
    """
    sender_contention: bool = False
    shaped_reward: bool = False
    receiver_contention: bool = False
    jittered_bandwidth: bool = False
    jitter_amp: float = 0.25   # only meaningful when jittered_bandwidth
    jitter_seed: int = 0       # only meaningful when jittered_bandwidth

    @property
    def mode_bits(self) -> int:
        """Communication modes packed into an int (store invalidation key).

        Bit 0: sender_contention, bit 1: receiver_contention, bit 2:
        jittered_bandwidth.  Backwards compatible with the historical
        boolean ``"cm"`` store field (0/1 ⇔ sender only).
        """
        return (int(self.sender_contention)
                | (int(self.receiver_contention) << 1)
                | (int(self.jittered_bandwidth) << 2))

    def comm_mode_kwargs(self) -> dict:
        """The communication-mode knobs as kwargs, for threading into
        ``serve.fingerprint.topology_fingerprint`` and friends."""
        return dict(sender_contention=self.sender_contention,
                    receiver_contention=self.receiver_contention,
                    jittered_bandwidth=self.jittered_bandwidth,
                    jitter_amp=self.jitter_amp,
                    jitter_seed=self.jitter_seed)


# lowbias32-style avalanche over a mix of edge coordinates: the jitter
# factor of a transfer is a pure function of (src node, dst node, src
# device, dst device, seed), so it is reproducible across the monolithic
# loop, the segmented loop, and the numpy oracle (which re-implements the
# same hash with python ints in repro/sim/reference.py).
JITTER_MIX = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1)


def jitter_factors(u: jnp.ndarray, v: jnp.ndarray, pu: jnp.ndarray,
                   pv: jnp.ndarray, amp: float, seed: int) -> jnp.ndarray:
    """Per-edge bandwidth jitter factors in ``[1, 1 + amp]`` (f32).

    Inputs broadcast (the scheduler passes ``u``/``pu`` as ``[N, K]`` and
    ``v``/``pv`` as ``[N, 1]``).  All arithmetic is uint32 with wraparound,
    so the value is bit-identical to the reference oracle's python-int
    implementation.
    """
    j1, j2, j3, j4, j5 = JITTER_MIX
    x = (u.astype(jnp.uint32) * jnp.uint32(j1)
         ^ v.astype(jnp.uint32) * jnp.uint32(j2)
         ^ pu.astype(jnp.uint32) * jnp.uint32(j3)
         ^ pv.astype(jnp.uint32) * jnp.uint32(j4)
         ^ jnp.uint32((int(seed) * j5) & 0xFFFFFFFF))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    unit = x.astype(jnp.float32) * jnp.float32(1.0 / 2 ** 32)
    return (1.0 + jnp.float32(amp) * unit).astype(jnp.float32)


class SimTopology(NamedTuple):
    """Device-side arrays of a Topology, ready for the jitted scheduler."""
    num_devices: int         # static python int
    inv_bw: jnp.ndarray      # f32[D, D] reciprocal bandwidth (diag 0)
    latency: jnp.ndarray     # f32[D, D] seconds (diag 0)
    mem_caps: jnp.ndarray    # f32[D] per-device capacity bytes

    @classmethod
    def from_topology(cls, topo: Topology) -> "SimTopology":
        """Convert a host-side Topology into device arrays (bw inverted
        once so the scheduler multiplies instead of divides)."""
        with np.errstate(divide="ignore"):
            inv_bw = (1.0 / topo.bw).astype(np.float32)
        return cls(topo.num_devices, jnp.asarray(inv_bw),
                   jnp.asarray(topo.latency.astype(np.float32)),
                   jnp.asarray(topo.mem_caps.astype(np.float32)))


class SimGraph(NamedTuple):
    """Device-ready padded arrays for one dataflow graph."""
    compute_t: jnp.ndarray   # f32[N, D]  per-(node, device) seconds
    out_bytes: jnp.ndarray   # f32[N]    producer output bytes
    mem_bytes: jnp.ndarray   # f32[N]
    in_idx: jnp.ndarray      # i32[N, K] padded with N (sentinel)
    in_mask: jnp.ndarray     # f32[N, K]
    node_mask: jnp.ndarray   # f32[N]    1 for real nodes


def prepare_sim_graph(g: DataflowGraph, topo: Topology, max_deg: int = 16,
                      pad_to: Optional[int] = None,
                      pad_k: Optional[int] = None,
                      pad_multiple: Optional[int] = None) -> SimGraph:
    """``pad_to``/``pad_k`` pin the node and in-edge dims (sentinel-padded)
    so graphs of different sizes share one compiled simulator — the serving
    path pads both to its bucket.  ``pad_multiple`` rounds the node dim up
    to a multiple (segment padding: the segment-batched ``simulate`` scans
    fixed-size segments, so the node dim must divide into them)."""
    n = g.num_nodes
    d = topo.num_devices
    pad_n = pad_to or n
    if pad_multiple:
        pad_n = ((pad_n + pad_multiple - 1) // pad_multiple) * pad_multiple
    assert pad_n >= n
    ct = node_compute_matrix(g, topo).astype(np.float32)
    idx, mask = g.in_neighbors_padded(max_deg)
    k = idx.shape[1]
    if pad_k is not None:
        assert pad_k >= k, (pad_k, k)
        k = pad_k
        idx = np.concatenate(
            [idx, np.full((n, pad_k - idx.shape[1]), n, np.int32)], axis=1)
        mask = np.concatenate(
            [mask, np.zeros((n, pad_k - mask.shape[1]), mask.dtype)], axis=1)

    compute_t = np.zeros((pad_n, d), np.float32)
    compute_t[:n] = ct
    out_b = np.zeros(pad_n, np.float32)
    out_b[:n] = g.out_bytes
    mem_b = np.zeros(pad_n, np.float32)
    mem_b[:n] = g.mem_bytes
    in_idx = np.full((pad_n, k), pad_n, np.int32)
    in_idx[:n] = np.where(idx == n, pad_n, idx)
    in_mask = np.zeros((pad_n, k), np.float32)
    in_mask[:n] = mask
    node_mask = np.zeros(pad_n, np.float32)
    node_mask[:n] = 1.0
    return SimGraph(jnp.asarray(compute_t), jnp.asarray(out_b), jnp.asarray(mem_b),
                    jnp.asarray(in_idx), jnp.asarray(in_mask), jnp.asarray(node_mask))


def simulate(sg: SimGraph, placement: jnp.ndarray, st: SimTopology,
             sender_contention: bool = False,
             segment: Optional[int] = None, *,
             receiver_contention: bool = False,
             jittered_bandwidth: bool = False,
             jitter_amp: float = 0.25, jitter_seed: int = 0
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (makespan_s, mem_util, valid).

    ``placement``: int32[N] in [0, st.num_devices).  Padded nodes
    contribute zero compute/memory so their placement is irrelevant.
    ``mem_util`` is max over devices of resident bytes / capacity; a
    placement is valid iff every device stays within its own cap.

    ``sender_contention=True`` serializes each device's outgoing
    transfers on a single send port (numpy-oracle semantics,
    ``reference.simulate_ref(..., sender_contention=True)``): transfer k
    out of device *d* starts at ``max(producer_finish, send_free[d])``
    and occupies the port for its duration.  Edges are consumed in the
    same padded in-neighbor order as the oracle, so makespans match it
    exactly.  ``receiver_contention=True`` is the mirror: incoming
    transfers serialize on the destination's receive port; with both on,
    a transfer waits for *both* ports and occupies both.  The contended
    inner loop is sequential per edge (the port state carries between
    edges), so prefer the default hoisted path when neither matters.

    ``jittered_bandwidth=True`` multiplies each cross-device transfer's
    duration by a deterministic factor in ``[1, 1 + jitter_amp]``
    (:func:`jitter_factors`); it composes with either contention mode
    and keeps the hoisted fast path when used alone.

    ``segment`` runs the segment-batched loop instead: the outer
    ``fori_loop`` walks ``N // segment`` segments and the body scans the
    nodes of one segment (N must divide; ``prepare_sim_graph`` pads with
    ``pad_multiple``).  The visit order — and therefore every float —
    is identical to the monolithic loop (pinned bit-for-bit by
    tests/test_segmented.py); what changes is the loop structure the
    large-graph mode audits and extends.
    """
    n = sg.compute_t.shape[0]
    p = placement.astype(jnp.int32)
    p_pad = jnp.concatenate([p, jnp.array([0], jnp.int32)])  # sentinel slot
    out_b_pad = jnp.concatenate([sg.out_bytes, jnp.zeros(1, jnp.float32)])
    # effective compute including the dev_free update guard
    ct_eff = sg.compute_t * sg.node_mask[:, None]                # [N, D]
    finish0 = jnp.zeros(n + 1, jnp.float32)   # sentinel row stays 0
    dev_free0 = jnp.zeros(st.num_devices, jnp.float32)

    pd = p_pad[sg.in_idx]                                        # [N, K]
    pv_col = p[:, None]
    jmat = None
    if jittered_bandwidth:
        v_idx = jnp.arange(n, dtype=jnp.int32)[:, None]          # [N, 1]
        jmat = jitter_factors(sg.in_idx, v_idx, pd, pv_col,
                              jitter_amp, jitter_seed)           # [N, K]

    if sender_contention or receiver_contention:
        k = sg.in_idx.shape[1]

        def body_c(v, state):
            finish, dev_free, send_free, recv_free = state
            pv = p[v]

            def edge(kk, acc):
                ready, sf, rf = acc
                u = sg.in_idx[v, kk]
                m = sg.in_mask[v, kk]
                pu = p_pad[u]
                t = finish[u]
                dur = out_b_pad[u] * st.inv_bw[pu, pv]
                if jmat is not None:
                    dur = dur * jmat[v, kk]
                start = t
                if sender_contention:
                    start = jnp.maximum(start, sf[pu])
                if receiver_contention:
                    start = jnp.maximum(start, rf[pv])
                crossing = (m > 0) & (pu != pv)
                if sender_contention:
                    sf = jnp.where(crossing, sf.at[pu].set(start + dur), sf)
                if receiver_contention:
                    rf = jnp.where(crossing, rf.at[pv].set(start + dur), rf)
                t_edge = jnp.where(pu != pv,
                                   start + st.latency[pu, pv] + dur, t)
                return (jnp.maximum(ready, jnp.where(m > 0, t_edge, 0.0)),
                        sf, rf)

            ready, send_free, recv_free = jax.lax.fori_loop(
                0, k, edge, (jnp.float32(0.0), send_free, recv_free))
            fin = jnp.maximum(ready, dev_free[pv]) + ct_eff[v, pv]
            return (finish.at[v].set(fin), dev_free.at[pv].set(fin),
                    send_free, recv_free)

        body_fn = body_c
        state0 = (finish0, dev_free0,
                  jnp.zeros(st.num_devices, jnp.float32),
                  jnp.zeros(st.num_devices, jnp.float32))
    else:
        # Everything except producer finish times is loop-independent:
        # hoist the per-edge communication cost out of the sequential scan
        # (the loop body is dispatch-overhead-bound on CPU; fewer ops per
        # step ≈ 2-3x faster).  Jitter is loop-independent too, so the
        # jitter-only mode keeps this path.
        cross = (pd != pv_col).astype(jnp.float32) * sg.in_mask
        dur_mat = out_b_pad[sg.in_idx] * st.inv_bw[pd, pv_col]     # [N, K]
        if jmat is not None:
            dur_mat = dur_mat * jmat
        comm = cross * (st.latency[pd, pv_col] + dur_mat)          # [N, K]

        def body(v, state):
            finish, dev_free = state
            ready = jnp.max(sg.in_mask[v] * finish[sg.in_idx[v]] + comm[v],
                            initial=0.0)
            pv = p[v]
            fin = jnp.maximum(ready, dev_free[pv]) + ct_eff[v, pv]
            return finish.at[v].set(fin), dev_free.at[pv].set(fin)

        body_fn = body
        state0 = (finish0, dev_free0)

    if segment is not None and n > segment:
        assert n % segment == 0, (n, segment)

        def seg_body(s, state):
            return jax.lax.fori_loop(s * segment, (s + 1) * segment,
                                     body_fn, state)

        state = jax.lax.fori_loop(0, n // segment, seg_body, state0)
    else:
        state = jax.lax.fori_loop(0, n, body_fn, state0)
    finish = state[0]
    makespan = jnp.max(finish[:n] * sg.node_mask)

    mem_used = jax.ops.segment_sum(sg.mem_bytes * sg.node_mask, p,
                                   num_segments=st.num_devices)
    util = jnp.max(mem_used / st.mem_caps)
    valid = jnp.all(mem_used <= st.mem_caps)
    return makespan, util, valid


def reward_from_runtime(makespan: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Paper §4.1: reward = −sqrt(runtime); −10 for invalid placements."""
    return jnp.where(valid, -jnp.sqrt(jnp.maximum(makespan, 1e-9)),
                     jnp.float32(INVALID_REWARD))


def reward_shaped(makespan: jnp.ndarray, mem_util: jnp.ndarray,
                  penalty: float = 5.0) -> jnp.ndarray:
    """Beyond-paper: continuous memory penalty instead of the −10 cliff.

    r = −sqrt(runtime) − penalty·max(0, util − 1), floored at −10, where
    util is the worst per-device capacity utilization.  The flat −10 gives
    no gradient *toward* validity; the shaped form does, which matters at
    CPU-scale trial budgets (EXPERIMENTS.md §Perf notes).  Valid placements
    score identically to the paper reward.
    """
    r = -jnp.sqrt(jnp.maximum(makespan, 1e-9)) - \
        penalty * jnp.maximum(mem_util - 1.0, 0.0)
    return jnp.maximum(r, jnp.float32(INVALID_REWARD))


def simulate_batch(sg: SimGraph, placements: jnp.ndarray, st: SimTopology,
                   shaped: bool = False, sender_contention: bool = False,
                   segment: Optional[int] = None, *,
                   receiver_contention: bool = False,
                   jittered_bandwidth: bool = False,
                   jitter_amp: float = 0.25, jitter_seed: int = 0
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """vmap over M placements: returns (makespan[M], reward[M], valid[M])."""
    fn = jax.vmap(lambda pl: simulate(
        sg, pl, st, sender_contention, segment=segment,
        receiver_contention=receiver_contention,
        jittered_bandwidth=jittered_bandwidth,
        jitter_amp=jitter_amp, jitter_seed=jitter_seed))
    makespan, util, valid = fn(placements)
    if shaped:
        return makespan, reward_shaped(makespan, util), valid
    return makespan, reward_from_runtime(makespan, valid), valid


@partial(jax.jit, static_argnames=("num_devices", "shaped",
                                   "sender_contention", "segment",
                                   "receiver_contention",
                                   "jittered_bandwidth",
                                   "jitter_amp", "jitter_seed"))
def _simulate_batch_jit(sg: SimGraph, placements, inv_bw, latency, mem_caps,
                        num_devices: int, shaped: bool,
                        sender_contention: bool,
                        segment: Optional[int] = None,
                        receiver_contention: bool = False,
                        jittered_bandwidth: bool = False,
                        jitter_amp: float = 0.25, jitter_seed: int = 0):
    """Stable-identity jitted wrapper so repeated Env.rewards calls with
    the same shapes hit the pjit cache instead of re-tracing the scan
    (eager fori_loop re-compiles per call — ~0.5 s each at serving sizes;
    SimTopology.num_devices must stay static, hence the unpacking)."""
    st = SimTopology(num_devices, inv_bw, latency, mem_caps)
    return simulate_batch(sg, placements, st, shaped=shaped,
                          sender_contention=sender_contention,
                          segment=segment,
                          receiver_contention=receiver_contention,
                          jittered_bandwidth=jittered_bandwidth,
                          jitter_amp=jitter_amp, jitter_seed=jitter_seed)


# one program per (shape, mode) — a compile-count regression here costs
# ~0.5 s per Env.rewards call at serving sizes, so it is watched
jaxprof.register("sim.simulate_batch", _simulate_batch_jit)


@dataclasses.dataclass(frozen=True)
class Env:
    """Bound environment: graph + topology, exposing jit-compiled rollout eval.

    ``shaped_reward`` / ``sender_contention`` mirror :class:`SimConfig`
    (``Env.from_config`` binds one); both are static jit keys, so envs
    with different modes compile separate programs and an env's numbers
    never silently change mode.
    """
    sg: SimGraph
    topo: Topology
    shaped_reward: bool = False
    sender_contention: bool = False
    receiver_contention: bool = False
    jittered_bandwidth: bool = False
    jitter_amp: float = 0.25
    jitter_seed: int = 0
    # Segment-batched evaluation (non-semantic: bit-identical makespans,
    # only the compiled loop structure changes).  The SimGraph's node dim
    # must be a multiple (prepare_sim_graph pad_multiple).
    segment: Optional[int] = None

    @classmethod
    def from_config(cls, sg: SimGraph, topo: Topology, sim: "SimConfig",
                    segment: Optional[int] = None) -> "Env":
        """Bind a graph + topology under one :class:`SimConfig`."""
        return cls(sg, topo, shaped_reward=sim.shaped_reward,
                   sender_contention=sim.sender_contention,
                   receiver_contention=sim.receiver_contention,
                   jittered_bandwidth=sim.jittered_bandwidth,
                   jitter_amp=sim.jitter_amp, jitter_seed=sim.jitter_seed,
                   segment=segment)

    @property
    def config(self) -> SimConfig:
        """The :class:`SimConfig` this env evaluates under."""
        return SimConfig(sender_contention=self.sender_contention,
                         shaped_reward=self.shaped_reward,
                         receiver_contention=self.receiver_contention,
                         jittered_bandwidth=self.jittered_bandwidth,
                         jitter_amp=self.jitter_amp,
                         jitter_seed=self.jitter_seed)

    @cached_property
    def sim_topology(self) -> SimTopology:
        """Device-side :class:`SimTopology` arrays (built once per env)."""
        return SimTopology.from_topology(self.topo)

    def rewards(self, placements: jnp.ndarray):
        """Evaluate M placements: returns (makespan[M], reward[M], valid[M]).

        Routes through a stable jitted wrapper so repeated calls with the
        same shapes and modes hit the pjit cache instead of re-tracing."""
        st = self.sim_topology
        return _simulate_batch_jit(self.sg, jnp.asarray(placements),
                                   st.inv_bw, st.latency, st.mem_caps,
                                   st.num_devices, self.shaped_reward,
                                   self.sender_contention, self.segment,
                                   self.receiver_contention,
                                   self.jittered_bandwidth,
                                   self.jitter_amp, self.jitter_seed)
