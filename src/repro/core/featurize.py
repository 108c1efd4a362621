"""Graph featurization: DataflowGraph -> padded arrays for the policy.

Node features (paper §3.1: "concatenation of meta features (e.g. operation
type, output shape, adjacent node ids)"):

* op type            -> embedding id (looked up inside the GNN)
* log-scaled flops / output bytes / resident bytes
* log in/out degree
* topological position fraction
* log output-shape dims (up to rank 4)

Device features (heterogeneous-topology extension): a ``[D, F]`` table of
normalized per-device capabilities — relative peak FLOP/s, HBM bandwidth,
memory capacity and interconnect reach — that conditions the decoder's
device logits so the policy can learn "put the big matmuls on the fast
device".  On a uniform pool every row is identical, so the table shifts
all valid devices' logits equally and the placement distribution reduces
to the homogeneous one.

Graphs in a batch are padded to a common (N, K); the sentinel neighbor index
is N (a zero/-inf feature row is appended where needed).
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from repro.core.graph import DataflowGraph, MAX_SHAPE_RANK
from repro.core.scale import ScaleConfig

NUM_NUMERIC_FEATURES = 6 + MAX_SHAPE_RANK
NUM_DEVICE_FEATURES = 6


class GraphBatch(NamedTuple):
    """One (optionally padded) graph ready for the policy network."""
    op: jnp.ndarray          # i32[N]
    feats: jnp.ndarray       # f32[N, F]
    nbr_idx: jnp.ndarray     # i32[N, K]   sentinel = N
    nbr_mask: jnp.ndarray    # f32[N, K]
    node_mask: jnp.ndarray   # f32[N]
    mem_frac: jnp.ndarray    # f32[N]  node resident bytes / tightest device cap
    comp_frac: jnp.ndarray   # f32[N]  best-device compute time / graph total
    dev_feats: jnp.ndarray   # f32[D, F_DEV] normalized per-device capabilities
    dev_mem_cap: jnp.ndarray  # f32[D] device cap / tightest cap (mem_frac units)
    num_nodes: int           # real node count (static python int)
    # BSR adjacency index for the CSR-blocked aggregation kernel
    # (kernels.csr_maxpool.BlockIndex), or None.  Built by ``featurize``
    # when ``scale.csr`` is set; ``pad_to_common`` drops it (re-padding
    # invalidates the tile geometry — re-featurize to rebuild).
    csr_blocks: Optional[Any] = None


def device_features(topo) -> np.ndarray:
    """f32[D, NUM_DEVICE_FEATURES] normalized capability table.

    Columns: peak-FLOP/s, HBM bandwidth and memory capacity relative to the
    pool's best device; mean and min outgoing link bandwidth relative to
    the pool's best-connected device; absolute log-FLOP/s anchor.
    """
    d = topo.num_devices
    pf, hb, mc = topo.peak_flops, topo.hbm_bw, topo.mem_caps
    off = ~np.eye(d, dtype=bool)
    if d > 1:
        bw_out = np.array([topo.bw[i][off[i]].mean() for i in range(d)])
        bw_min = np.array([topo.bw[i][off[i]].min() for i in range(d)])
    else:
        bw_out = bw_min = np.ones(d)
    f = np.stack([pf / pf.max(), hb / hb.max(), mc / mc.max(),
                  bw_out / bw_out.max(), bw_min / bw_min.max(),
                  np.log10(pf) / 15.0], axis=1)
    return f.astype(np.float32)


def featurize(g: DataflowGraph, max_deg: int = 8,
              pad_to: Optional[int] = None, topo=None,
              csr_block_n: int = 64, csr_block_m: int = 128,
              scale: ScaleConfig = ScaleConfig()) -> GraphBatch:
    """``topo`` (sim.device.Topology) enables the resource-aware decoder
    context: per-node memory/compute fractions the AR placer accumulates
    per device while decoding, plus the per-device capability table
    (DESIGN.md §5-addendum).  ``scale``
    (:class:`repro.core.scale.ScaleConfig`) supplies the padding grid
    (``scale.pad_multiple`` rounds the padded node dim up to a multiple —
    segment-native pipelines pad to the decode segment so every segment
    has one compiled shape) and ``scale.csr`` (build the BSR adjacency
    block index, O(edges) numpy work done once per graph, so the GNN can
    aggregate via the CSR-blocked kernel,
    ``PolicyConfig.agg_impl="pallas_csr"``)."""
    n = g.num_nodes
    pad_n = pad_to or n
    if scale.pad_multiple:
        m = scale.pad_multiple
        pad_n = ((pad_n + m - 1) // m) * m
    assert pad_n >= n, (pad_n, n)

    f = np.zeros((pad_n, NUM_NUMERIC_FEATURES), np.float32)
    f[:n, 0] = np.log1p(g.flops) / 30.0
    f[:n, 1] = np.log1p(g.out_bytes) / 30.0
    f[:n, 2] = np.log1p(g.mem_bytes) / 30.0
    f[:n, 3] = np.log1p(g.in_degree()) / 5.0
    f[:n, 4] = np.log1p(g.out_degree()) / 5.0
    f[:n, 5] = np.arange(n, dtype=np.float32) / max(n - 1, 1)
    f[:n, 6:6 + MAX_SHAPE_RANK] = np.log1p(g.out_shape) / 20.0

    idx, mask = g.all_neighbors_padded(max_deg)
    k = idx.shape[1]
    nbr_idx = np.full((pad_n, k), pad_n, np.int32)
    nbr_idx[:n] = np.where(idx == n, pad_n, idx)
    nbr_mask = np.zeros((pad_n, k), np.float32)
    nbr_mask[:n] = mask

    op = np.zeros(pad_n, np.int32)
    op[:n] = g.op_type
    node_mask = np.zeros(pad_n, np.float32)
    node_mask[:n] = 1.0

    mem_frac = np.zeros(pad_n, np.float32)
    comp_frac = np.zeros(pad_n, np.float32)
    dev_feats = np.zeros((0, NUM_DEVICE_FEATURES), np.float32)
    dev_mem_cap = np.zeros(0, np.float32)
    if topo is not None:
        from repro.sim.cost_model import node_compute_matrix
        # fractions against the tightest cap / best device: identical to
        # the historical single-spec fractions on uniform pools.  The
        # tightest cap is the tightest POSITIVE cap — a failed device
        # (sim.chaos: capacity 0) must not zero the denominator; it gets
        # dev_mem_cap 0 below, so the memory-aware decode closes it.
        caps = topo.mem_caps
        alive = caps[caps > 0]
        tight = alive.min() if alive.size else 1.0
        mem_frac[:n] = g.mem_bytes / tight
        ct = node_compute_matrix(g, topo).min(axis=1)
        comp_frac[:n] = ct / max(ct.sum(), 1e-12)
        dev_feats = device_features(topo)
        # per-device caps in mem_frac units: the decoder's running
        # accumulators compare directly against these (memory-aware
        # masked decode, PolicyConfig.mask_full_devices)
        dev_mem_cap = (caps / tight).astype(np.float32)
    blocks = None
    if scale.csr:
        from repro.kernels.csr_maxpool import build_block_index
        blocks = build_block_index(nbr_idx, nbr_mask, pad_n,
                                   block_n=csr_block_n, block_m=csr_block_m)
    return GraphBatch(jnp.asarray(op), jnp.asarray(f), jnp.asarray(nbr_idx),
                      jnp.asarray(nbr_mask), jnp.asarray(node_mask),
                      jnp.asarray(mem_frac), jnp.asarray(comp_frac),
                      jnp.asarray(dev_feats), jnp.asarray(dev_mem_cap), n,
                      blocks)


class _ColsView(NamedTuple):
    """Duck-typed stand-in for DataflowGraph inside the cost model (which
    only reads op_type / flops / out_bytes / num_nodes)."""
    op_type: np.ndarray
    flops: np.ndarray
    out_bytes: np.ndarray
    num_nodes: int


def _window_neighbors(edges, lo: int, hi: int, k: int, pad_n: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Padded neighbor matrix for window ``[lo, hi)`` from a shard edge
    range ``(key, nbr, w)`` sorted by (key, nbr).

    Replicates ``graph._padded_neighbors`` exactly — same grouping order,
    same keep-heaviest truncation (``w`` is the per-edge copy of the
    weight that function looks up) — then remaps neighbor ids into window
    coordinates; neighbors outside the window become masked sentinels
    (their features live in other windows)."""
    key, nbr, w = edges
    n = hi - lo
    idx = np.full((pad_n, k), pad_n, np.int32)
    mask = np.zeros((pad_n, k), np.float32)
    starts = np.searchsorted(key, np.arange(lo, hi))
    ends = np.searchsorted(key, np.arange(lo, hi) + 1)
    for v in range(n):
        nb, wt = nbr[starts[v]:ends[v]], w[starts[v]:ends[v]]
        if nb.size > k:
            sel = np.argsort(-wt, kind="stable")[:k]
            nb = nb[sel]
        inside = (nb >= lo) & (nb < hi)
        idx[v, :nb.size] = np.where(inside, nb - lo, pad_n)
        mask[v, :nb.size] = inside
    return idx, mask


def featurize_window(shards, lo: int = 0, hi: Optional[int] = None,
                     max_deg: int = 8, pad_to: Optional[int] = None,
                     topo=None,
                     scale: ScaleConfig = ScaleConfig()) -> GraphBatch:
    """Out-of-core :func:`featurize`: one window ``[lo, hi)`` of a
    sharded graph (:class:`repro.graphs.shards.GraphShards`), without
    ever materializing whole-graph feature/neighbor arrays.

    Over the full window this is bit-identical to
    ``featurize(shards.load_graph(), ...)`` (pinned by tests/test_hier.py):
    degree features are the stored *global* degrees, the topo-position
    column uses global node ids, the neighbor matrices keep the global
    padded width (from the shard meta's degree maxima, so every window of
    one graph shares a compiled shape) and the exact stable-sort /
    keep-heaviest truncation order of the in-RAM path, and ``comp_frac``
    is normalized by the whole-graph compute total (summed in one
    ``np.sum`` over the full column — no per-chunk reassociation).
    Neighbors that fall outside the window are masked out; the
    hierarchical refiner compensates by fixing their assignments as
    incumbents.  ``scale.pad_multiple`` rounds the padded window length;
    ``scale.csr`` is ignored (windows aggregate via the chunked path).
    """
    n_all = shards.num_nodes
    hi = n_all if hi is None else hi
    assert 0 <= lo <= hi <= n_all, (lo, hi, n_all)
    n = hi - lo
    pad_n = pad_to or n
    if scale.pad_multiple:
        m = scale.pad_multiple
        pad_n = ((pad_n + m - 1) // m) * m
    assert pad_n >= n, (pad_n, n)

    nd = shards.nodes(lo, hi)
    f = np.zeros((pad_n, NUM_NUMERIC_FEATURES), np.float32)
    f[:n, 0] = np.log1p(nd["flops"]) / 30.0
    f[:n, 1] = np.log1p(nd["out_bytes"]) / 30.0
    f[:n, 2] = np.log1p(nd["mem_bytes"]) / 30.0
    f[:n, 3] = np.log1p(nd["in_degree"]) / 5.0
    f[:n, 4] = np.log1p(nd["out_degree"]) / 5.0
    f[:n, 5] = (np.arange(lo, hi, dtype=np.float32)
                / max(n_all - 1, 1))
    f[:n, 6:6 + MAX_SHAPE_RANK] = np.log1p(nd["out_shape"]) / 20.0

    k_in = max(min(int(shards.meta["max_in_degree"]), max_deg), 1)
    k_out = max(min(int(shards.meta["max_out_degree"]), max_deg), 1)
    s_i, d_i, w_i = shards.in_edges(lo, hi)
    ii, mi = _window_neighbors((d_i, s_i, w_i), lo, hi, k_in, pad_n)
    s_o, d_o, w_o = shards.out_edges(lo, hi)
    oo, mo = _window_neighbors((s_o, d_o, w_o), lo, hi, k_out, pad_n)
    nbr_idx = np.concatenate([ii, oo], axis=1)
    nbr_mask = np.concatenate([mi, mo], axis=1)

    op = np.zeros(pad_n, np.int32)
    op[:n] = nd["op_type"]
    node_mask = np.zeros(pad_n, np.float32)
    node_mask[:n] = 1.0

    mem_frac = np.zeros(pad_n, np.float32)
    comp_frac = np.zeros(pad_n, np.float32)
    dev_feats = np.zeros((0, NUM_DEVICE_FEATURES), np.float32)
    dev_mem_cap = np.zeros(0, np.float32)
    if topo is not None:
        from repro.sim.cost_model import node_compute_matrix
        caps = topo.mem_caps
        alive = caps[caps > 0]
        tight = alive.min() if alive.size else 1.0
        mem_frac[:n] = nd["mem_bytes"] / tight
        # global compute total: full scalar columns (cached on the shard
        # handle) through the same cost-model code as the in-RAM path
        view = _ColsView(shards.column("op_type"), shards.column("flops"),
                         shards.column("out_bytes"), n_all)
        ct = node_compute_matrix(view, topo).min(axis=1)
        comp_frac[:n] = ct[lo:hi] / max(ct.sum(), 1e-12)
        dev_feats = device_features(topo)
        dev_mem_cap = (caps / tight).astype(np.float32)
    return GraphBatch(jnp.asarray(op), jnp.asarray(f), jnp.asarray(nbr_idx),
                      jnp.asarray(nbr_mask), jnp.asarray(node_mask),
                      jnp.asarray(mem_frac), jnp.asarray(comp_frac),
                      jnp.asarray(dev_feats), jnp.asarray(dev_mem_cap), n)


# Padded-size ladder for micro-batched serving: bucketing request graphs
# to a few canonical sizes keeps the number of distinct compiled shapes
# (and therefore jit recompiles) bounded regardless of workload mix.
BUCKET_SIZES = (32, 64, 128, 256, 512, 1024, 2048, 4096)


def bucket_size(n: int, buckets: Tuple[int, ...] = BUCKET_SIZES) -> int:
    """Smallest bucket >= n; beyond the ladder, the next power of two."""
    for b in buckets:
        if n <= b:
            return b
    out = buckets[-1]
    while out < n:
        out *= 2
    return out


def jumbo_bucket(n: int, multiple: int = 2048) -> int:
    """Padded size for jumbo graphs: the next multiple of ``multiple``.

    Past the ladder, power-of-two buckets waste up to ~50% padding on a
    50k-node graph; a segmented decoder only needs the node dim to be a
    multiple of its segment, so the serving tier pads jumbo admissions to
    this much tighter grid instead (``ScaleConfig.jumbo_pad_multiple``).
    """
    return ((n + multiple - 1) // multiple) * multiple


def pad_to_common(batches: List[GraphBatch],
                  pad_n: Optional[int] = None, pad_k: Optional[int] = None,
                  pad_d: Optional[int] = None) -> List[GraphBatch]:
    """Re-pad a list of GraphBatches to identical (N, K, D) for stacking.

    Explicit ``pad_n/pad_k/pad_d`` targets (must dominate every batch)
    override the per-list maxima — the serving batcher pins them to bucket
    sizes so every flush of a bucket reuses one compiled shape.

    Padding runs in numpy (the serving hot path calls this per request;
    eager jnp scatter ops would pay an XLA dispatch — and a first-call
    compile — per field); ``stack_batches`` converts to device arrays once.
    """
    n = max(max(b.op.shape[0] for b in batches), pad_n or 0)
    k = max(max(b.nbr_idx.shape[1] for b in batches), pad_k or 0)
    d = max(max(b.dev_feats.shape[0] for b in batches), pad_d or 0)
    out = []
    for b in batches:
        bn, bk, bd = b.op.shape[0], b.nbr_idx.shape[1], b.dev_feats.shape[0]
        op = np.zeros(n, np.int32)
        op[:bn] = np.asarray(b.op)
        feats = np.zeros((n, b.feats.shape[1]), np.float32)
        feats[:bn] = np.asarray(b.feats)
        idx = np.full((n, k), n, np.int32)
        # remap old sentinel (bn) -> new sentinel (n)
        old = np.asarray(b.nbr_idx)
        idx[:bn, :bk] = np.where(old == bn, n, old)
        mask = np.zeros((n, k), np.float32)
        mask[:bn, :bk] = np.asarray(b.nbr_mask)
        nmask = np.zeros(n, np.float32)
        nmask[:bn] = np.asarray(b.node_mask)
        memf = np.zeros(n, np.float32)
        memf[:bn] = np.asarray(b.mem_frac)
        compf = np.zeros(n, np.float32)
        compf[:bn] = np.asarray(b.comp_frac)
        df = np.zeros((d, NUM_DEVICE_FEATURES), np.float32)
        if bd:
            df[:bd] = np.asarray(b.dev_feats)
        dmc = np.zeros(d, np.float32)   # padded devices: cap 0 (never used)
        if b.dev_mem_cap.shape[0]:
            dmc[:b.dev_mem_cap.shape[0]] = np.asarray(b.dev_mem_cap)
        out.append(GraphBatch(op, feats, idx, mask, nmask, memf, compf, df,
                              dmc, b.num_nodes))
    return out


def stack_batches(batches: List[GraphBatch],
                  pad_n: Optional[int] = None, pad_k: Optional[int] = None,
                  pad_d: Optional[int] = None) -> GraphBatch:
    """Stack equal-shape GraphBatches along a leading axis (for GDP-batch
    training and micro-batched serving; see ``pad_to_common`` for the
    bucketed-padding targets)."""
    padded = pad_to_common(batches, pad_n, pad_k, pad_d)

    def stk(field):
        return jnp.asarray(np.stack([np.asarray(getattr(b, field))
                                     for b in padded]))

    return GraphBatch(
        op=stk("op"), feats=stk("feats"), nbr_idx=stk("nbr_idx"),
        nbr_mask=stk("nbr_mask"), node_mask=stk("node_mask"),
        mem_frac=stk("mem_frac"), comp_frac=stk("comp_frac"),
        dev_feats=stk("dev_feats"), dev_mem_cap=stk("dev_mem_cap"),
        num_nodes=max(b.num_nodes for b in padded),
    )
