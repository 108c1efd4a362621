"""Plain list scheduler in float32 numpy: the reference for the program's
simulator, in the precision the configuration states for it.

Nodes run in topological order.  A node is ready once every kept
in-neighbor has finished and its output has crossed the link (latency
plus bytes over bandwidth, nothing when both sit on one device); it then
waits for its device to be free and runs for its compute time.  A
placement is valid when every device's resident bytes stay within its
cap.  Vectorized over the M placements of one call.  Every time is a
float32 sum taken in node order, so the clock rounds as the
configuration's float32 simulator does (at 54k nodes float32 sits about
3e-4 above float64, and the PPO advantages compare makespans of samples
closer than that).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

INVALID_REWARD = -10.0
MEMORY_PENALTY = 5.0


def simulate(si: Dict[str, np.ndarray], placements: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(makespan[M], worst memory use over cap[M], valid[M]) of the
    placements i[M, N] (real nodes only)."""
    p = np.asarray(placements, np.int64)
    m, n = p.shape
    d = si["caps"].shape[0]
    if p.size and (p.min() < 0 or p.max() >= d):
        raise ValueError(f"placement outside devices 0..{d - 1}")
    f32 = np.float32
    idx, mask = si["in_idx"], si["in_mask"]
    idx_c = np.minimum(idx, n - 1)                       # sentinel -> any row
    pu = p[:, idx_c]                                     # [M, N, K]
    pv = p[:, :, None]
    cross = mask[None] & (pu != pv)
    dur = si["out_bytes"].astype(f32)[idx_c][None] * \
        si["inv_bw"].astype(f32)[pu, pv]
    comm = np.where(cross, si["lat"].astype(f32)[pu, pv] + dur, f32(0))
    ct = si["ct"].astype(f32)[np.arange(n)[None, :], p]  # [M, N]
    finish = np.zeros((m, n), f32)
    dev_free = np.zeros((m, d), f32)
    rows = np.arange(m)
    for v in range(n):
        nb = idx_c[v]
        ready = np.where(mask[v][None], finish[:, nb] + comm[:, v], f32(0))
        start = np.maximum(ready.max(axis=1, initial=f32(0)),
                           dev_free[rows, p[:, v]])
        fin = start + ct[:, v]
        finish[:, v] = fin
        dev_free[rows, p[:, v]] = fin
    mem = np.zeros((m, d), f32)
    for i in range(m):
        np.add.at(mem[i], p[i], si["mem_bytes"].astype(f32))
    caps = si["caps"].astype(f32)[None]
    util = (mem / caps).max(axis=1)
    valid = np.all(mem <= caps, axis=1)
    return finish.max(axis=1), util, valid


def shaped_reward(makespan: np.ndarray, util: np.ndarray) -> np.ndarray:
    """-sqrt(makespan) less a penalty on memory use past the cap, never
    below the invalid reward."""
    f32 = np.float32
    r = -np.sqrt(np.maximum(makespan, f32(1e-9))) - \
        f32(MEMORY_PENALTY) * np.maximum(util - f32(1), f32(0))
    return np.maximum(r, f32(INVALID_REWARD)).astype(f32)
