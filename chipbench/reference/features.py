"""Plain featurization of a dataflow graph for the reference policy and
simulator.

Written from the GDP policy's published inputs (op type, log-scaled costs,
degrees, topological position, output shape; per-device capability rows)
and the list scheduler's cost model, with numpy only.  It imports nothing
of the program: the graph object is read through its public arrays
(``op_type``, ``flops``, ``out_bytes``, ``mem_bytes``, ``out_shape``,
``src``, ``dst``), and the fleet comes from the configuration file.

Everything here covers the real nodes only: padding is how the program
batches, not part of the model.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

# op-type vocabulary of the dataflow IR, in id order; ids index the GNN's
# op embedding and the cost model's efficiency table
OP_TYPES = (
    "parameter", "input", "matmul", "conv", "depthwise_conv", "elementwise",
    "reduce", "softmax", "embedding", "lstm_cell", "attention", "layernorm",
    "concat", "split", "transpose", "reshape", "gather", "scatter", "pool",
    "loss", "update", "collective", "dynamic_slice", "scan", "other")
MAX_SHAPE_RANK = 4
NUM_NUMERIC_FEATURES = 6 + MAX_SHAPE_RANK
NUM_DEVICE_FEATURES = 6

# fraction of peak FLOP/s each op class reaches in the list scheduler's
# roofline cost model; classes not listed run at DEFAULT_EFF
EFF = {"matmul": 0.62, "conv": 0.55, "depthwise_conv": 0.12,
       "lstm_cell": 0.5, "attention": 0.45, "embedding": 0.05,
       "softmax": 0.08, "reduce": 0.08, "elementwise": 0.06,
       "layernorm": 0.08, "pool": 0.10, "loss": 0.08, "update": 0.06,
       "gather": 0.04, "scatter": 0.04, "scan": 0.3}
DEFAULT_EFF = 0.08
OP_OVERHEAD_S = 4e-6


def fleet_arrays(fleet: Dict) -> Dict[str, np.ndarray]:
    """A uniform fleet as arrays: per-device peak, HBM bandwidth and
    memory cap; [D, D] link bandwidth (inf on the diagonal) and latency
    (0 on the diagonal).  ``fleet["mem_caps"]`` is a list of caps."""
    d = int(fleet["num_devices"])
    bw = np.full((d, d), float(fleet["link_bw"]))
    lat = np.full((d, d), float(fleet["link_latency_s"]))
    np.fill_diagonal(bw, np.inf)
    np.fill_diagonal(lat, 0.0)
    return {"num_devices": d,
            "peak": np.full(d, float(fleet["peak_flops"])),
            "hbm": np.full(d, float(fleet["hbm_bw"])),
            "caps": np.asarray(fleet["mem_caps"], np.float64),
            "bw": bw, "lat": lat}


def padded_neighbors(key: np.ndarray, val: np.ndarray, n: int,
                     weight: np.ndarray, max_deg: int):
    """Neighbor lists padded to ``k = min(max degree, max_deg)`` columns
    (sentinel ``n``); a node with more than ``k`` neighbors keeps the ``k``
    heaviest by ``weight`` (ties: the earlier edge in id order)."""
    deg = np.bincount(key, minlength=n)
    k = max(min(int(deg.max()) if deg.size and deg.max() > 0 else 1,
                max_deg), 1)
    idx = np.full((n, k), n, np.int64)
    mask = np.zeros((n, k), bool)
    order = np.argsort(key, kind="stable")
    ks, vs = key[order], val[order]
    starts = np.searchsorted(ks, np.arange(n))
    ends = np.searchsorted(ks, np.arange(n) + 1)
    for v in np.nonzero(deg)[0]:
        nb = vs[starts[v]:ends[v]]
        if nb.size > k:
            nb = nb[np.argsort(-weight[nb], kind="stable")[:k]]
        idx[v, :nb.size] = nb
        mask[v, :nb.size] = True
    return idx, mask


def compute_times(g, fl: Dict) -> np.ndarray:
    """float64[N, D] seconds of node i on device d: the larger of its
    FLOPs at the class efficiency and 3x its output bytes at HBM speed,
    plus a fixed per-op overhead; parameters and inputs cost nothing."""
    eff = np.array([EFF.get(t, DEFAULT_EFF) for t in OP_TYPES])[g.op_type]
    t_f = g.flops[:, None] / (fl["peak"][None, :] * eff[:, None])
    t_m = 3.0 * g.out_bytes[:, None] / fl["hbm"][None, :]
    t = np.maximum(t_f, t_m) + OP_OVERHEAD_S
    static = (g.flops == 0) & np.isin(g.op_type, [0, 1])
    return np.where(static[:, None], 0.0, t)


def device_features(fl: Dict) -> np.ndarray:
    """[D, 6] capability rows: peak, HBM bandwidth and memory relative to
    the best device; mean and min outgoing link bandwidth relative to the
    best-connected device; log10 peak / 15."""
    d = fl["num_devices"]
    off = ~np.eye(d, dtype=bool)
    bw_out = np.array([fl["bw"][i][off[i]].mean() for i in range(d)])
    bw_min = np.array([fl["bw"][i][off[i]].min() for i in range(d)])
    pf, hb, mc = fl["peak"], fl["hbm"], fl["caps"]
    return np.stack([pf / pf.max(), hb / hb.max(), mc / mc.max(),
                     bw_out / bw_out.max(), bw_min / bw_min.max(),
                     np.log10(pf) / 15.0], axis=1).astype(np.float32)


def policy_inputs(g, fl: Dict, max_deg: int = 8) -> Dict[str, np.ndarray]:
    """Inputs of the GDP policy for the graph's real nodes."""
    n = g.num_nodes
    indeg = np.bincount(g.dst, minlength=n)
    outdeg = np.bincount(g.src, minlength=n)
    f = np.zeros((n, NUM_NUMERIC_FEATURES), np.float32)
    f[:, 0] = np.log1p(g.flops) / 30.0
    f[:, 1] = np.log1p(g.out_bytes) / 30.0
    f[:, 2] = np.log1p(g.mem_bytes) / 30.0
    f[:, 3] = np.log1p(indeg) / 5.0
    f[:, 4] = np.log1p(outdeg) / 5.0
    f[:, 5] = np.arange(n, dtype=np.float32) / max(n - 1, 1)
    f[:, 6:] = np.log1p(g.out_shape) / 20.0
    ii, mi = padded_neighbors(g.dst, g.src, n, g.out_bytes, max_deg)
    oo, mo = padded_neighbors(g.src, g.dst, n, g.out_bytes, max_deg)
    caps = fl["caps"]
    tight = caps[caps > 0].min()
    ct = compute_times(g, fl).min(axis=1)
    return {"op": np.asarray(g.op_type, np.int32), "feats": f,
            "nbr_idx": np.concatenate([ii, oo], 1).astype(np.int32),
            "nbr_mask": np.concatenate([mi, mo], 1),
            "mem_frac": (g.mem_bytes / tight).astype(np.float32),
            "comp_frac": (ct / max(ct.sum(), 1e-12)).astype(np.float32),
            "dev_feats": device_features(fl),
            "dev_mem_cap": (caps / tight).astype(np.float32)}


def sim_inputs(g, fl: Dict, max_deg: int = 16) -> Dict[str, np.ndarray]:
    """Inputs of the list scheduler for the graph's real nodes."""
    idx, mask = padded_neighbors(g.dst, g.src, g.num_nodes, g.out_bytes,
                                 max_deg)
    with np.errstate(divide="ignore"):
        inv_bw = 1.0 / fl["bw"]
    return {"ct": compute_times(g, fl), "out_bytes": g.out_bytes,
            "mem_bytes": g.mem_bytes, "in_idx": idx, "in_mask": mask,
            "inv_bw": inv_bw, "lat": fl["lat"], "caps": fl["caps"]}


def pad_policy_inputs(inp: Dict[str, np.ndarray], n_pad: int
                      ) -> Dict[str, np.ndarray]:
    """The inputs with edge-free zero nodes appended up to ``n_pad`` and a
    ``node_mask`` marking the real ones (one compiled shape per size)."""
    n = inp["op"].shape[0]
    extra = n_pad - n

    def pad(a):
        return np.concatenate([a, np.zeros((extra,) + a.shape[1:], a.dtype)])

    out = dict(inp)
    for k in ("op", "feats", "nbr_idx", "nbr_mask", "mem_frac", "comp_frac"):
        out[k] = pad(inp[k])
    out["node_mask"] = (np.arange(n_pad) < n).astype(np.float32)
    return out
