"""Open-loop request traffic made from a mix file and the seed.

A serving mix (``chipbench/traffic/<mix>.json``) gives the arrival rate,
the Zipf skew over its model families (listed most popular first) and,
per family, the revisions a user may ask to place: generator keyword
sets with the node count each produces.  For a window of ``seconds``:

* ``n = round(rate * seconds)`` requests;
* the families' shares of ``n`` are fixed by Zipf(skew) over their ranks
  (largest remainder), and each family's revisions are taken evenly
  spread over its node counts, every one at most once;
* the inter-arrival gaps are the ``n`` quantiles of the exponential
  distribution at that rate, scaled to end inside the window;
* requests and gaps are put in one fixed pseudo-random order.

So every seed sends the same requests at the same times in the same
order: the schedule takes nothing from the seed, which picks only the
weights, the sampling and the answers checked: the order of arrival,
which moves the queueing and so the latency, is the same in every run.
``enumerate_revisions`` lists a family's revisions from a
grid, keeping those inside the node range whose round-robin placement
fits the fleet's memory (used to write the mix files).
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, List, Tuple

import numpy as np

Request = Tuple[float, str, Dict[str, Any]]      # due s, family, kwargs
ORDER_SEED = 0                                   # the one fixed schedule


def family_counts(n: int, families: int, skew: float) -> List[int]:
    """Requests per family rank under Zipf(skew), summing to ``n``."""
    w = np.arange(1, families + 1, dtype=np.float64) ** -skew
    share = n * w / w.sum()
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def spread(items: List[Any], k: int) -> List[Any]:
    """``k`` distinct items evenly spread over the (sorted) list."""
    if k > len(items):
        raise ValueError(f"mix lists {len(items)} revisions, {k} needed")
    return [items[int((i + 0.5) * len(items) / k)] for i in range(k)]


def window_requests(mix: Dict[str, Any], seconds: float) -> List[Request]:
    """The requests due in a window of ``seconds``, in arrival order."""
    rate = float(mix["rate_rps"])
    n = max(int(round(rate * seconds)), 1)
    fams = mix["families"]
    picked: List[Tuple[str, Dict[str, Any]]] = []
    for fam, k in zip(fams, family_counts(n, len(fams),
                                          float(mix["zipf_skew"]))):
        revs = sorted(fam["revisions"], key=lambda r: r["nodes"])
        picked += [(fam["family"], r["kwargs"]) for r in spread(revs, k)]
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds * (n - 0.5) / n / gaps.sum()
    rng = np.random.default_rng(ORDER_SEED)
    order = rng.permutation(n)
    gp = rng.permutation(gaps)
    due = np.cumsum(gp) - gp[0] / 2
    return [(float(due[i]), *picked[order[i]]) for i in range(n)]


def enumerate_revisions(family: str, grid: Dict[str, List[Any]],
                        fixed: Dict[str, Any], node_range: Tuple[int, int],
                        fits) -> List[Dict[str, Any]]:
    """Revisions of ``family`` over the grid: ``{"kwargs", "nodes"}`` for
    each keyword set whose graph has a node count inside ``node_range``
    and for which ``fits(graph)`` holds."""
    from repro.graphs import synthetic
    fn = getattr(synthetic, family)
    keys = sorted(grid)
    out = []
    for vals in itertools.product(*(grid[k] for k in keys)):
        kw = dict(fixed, **dict(zip(keys, vals)))
        g = fn(**kw)
        if node_range[0] <= g.num_nodes <= node_range[1] and fits(g):
            out.append({"kwargs": kw, "nodes": int(g.num_nodes)})
    return out
