"""FLOPs the GDP policy requires, counted from shapes.

Only the matrix products count (2 x multiply-adds): dense layers and the
two attention contractions.  Elementwise work, the neighbor max, padding
and anything recomputed under ``jax.checkpoint`` do not.  A node at
position ``i`` attends to ``min(i + 1, window)`` nodes; the program may
compute the masked rest of its window, which is padding.
"""
from __future__ import annotations

from typing import Any, Dict


def _dims(p: Dict[str, Any]):
    return (int(p["hidden"]), int(p["gnn_layers"]), int(p["op_emb"]),
            int(p["num_numeric_features"]), int(p["placer_layers"]),
            int(p["ffn"]), int(p["window"]), int(p["max_devices"]),
            int(p["num_device_features"]))


def encoder(n: int, p: Dict[str, Any]) -> float:
    """GraphSAGE encoder over ``n`` nodes plus the superposition gain."""
    h, gl, emb, nf = _dims(p)[:4]
    per_node = 2 * (emb + nf) * h + gl * (2 * h * h + 2 * 2 * h * h)
    gain = 2 * (2 * h) * h + 2 * h * h
    return float(n * per_node + (gain if p["use_superposition"] else 0))


def attended(n: int, window: int, count_masked: bool = False) -> int:
    """Sum over positions of the nodes each one attends to."""
    if count_masked:
        return n * min(window, n)
    w = min(window, n)
    return w * (w + 1) // 2 + (n - w) * w


def placer(n: int, p: Dict[str, Any], count_masked: bool = False) -> float:
    """One pass of the placer over ``n`` nodes: teacher-forced, or the
    autoregressive decode of one placement (the same products per node)."""
    h, _, _, _, layers, ffn, window, dmax, ndf = _dims(p)
    per_node = (2 * (2 * dmax + 2) * h
                + layers * (3 * 2 * h * h + 2 * h * h + 2 * 2 * h * ffn)
                + 2 * h * dmax + 2 * h * dmax)
    att = layers * 2 * 2 * h * attended(n, window, count_masked)
    return float(n * per_node + att + 2 * dmax * ndf * h)


def sample(n: int, p: Dict[str, Any], samples: int) -> float:
    """Encoder once, then ``samples`` autoregressive decodes."""
    return encoder(n, p) + samples * placer(n, p)


def ppo_iteration(n: int, p: Dict[str, Any], samples: int,
                  epochs: int) -> float:
    """One PPO iteration: sample, teacher-forced re-score of the relabelled
    placements, and per epoch the update's forward and backward (backward
    = 2x forward)."""
    forward = encoder(n, p) + samples * placer(n, p)
    return sample(n, p, samples) + forward + epochs * 3 * forward
