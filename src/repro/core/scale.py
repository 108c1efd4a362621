"""One home for every graph-size knob: :class:`ScaleConfig`.

Segmented decode, the chunked GNN gather, the featurizer's padding grid,
the serving tier's jumbo bucket and the hierarchy thresholds all bound
compiled shapes or peak memory by graph size.  They live in one frozen
dataclass, set once as ``PolicyConfig(scale=...)``; every layer reads
them from the policy's config (``featurize`` and ``gnn.apply`` take the
same object as ``scale=``).

The hierarchical coarsen→place→refine pipeline (``repro.hier``) adds
its own knobs here too — ``hier_threshold`` is where ``repro.api.place``
switches from the flat segmented path to the two-level one, and
``coarse_target``/``refine_window`` size the coarse graph and the
streamed refinement windows.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ScaleConfig:
    """Every knob that bounds compiled shapes / peak memory vs graph size.

    Attributes
    ----------
    segment:          segmented decode length (``None`` = monolithic);
                      one compiled per-segment program serves any graph.
    gnn_chunk:        chunked GNN neighbor gather (``None`` = one-shot);
                      bounds the [chunk, K, H] gather intermediate.
    pad_multiple:     featurization pads the node dim up to a multiple
                      (segment-native pipelines pad to the segment).
    csr:              build the BSR adjacency index during featurization
                      (``PolicyConfig.agg_impl="pallas_csr"``).
    jumbo_threshold:  serving tier: graphs above this skip the
                      micro-batcher and take the solo jumbo path.
    jumbo_pad_multiple: padding grid for jumbo admissions
                      (``featurize.jumbo_bucket``).
    hier_threshold:   ``repro.api.place`` routes graphs above this
                      through coarsen→place→refine (``repro.hier``).
    coarse_target:    target super-node count for the coarsener.
    refine_window:    fine nodes re-decoded per refinement step; peak
                      policy RSS is bounded by this, not by graph size.
    """
    segment: Optional[int] = None
    gnn_chunk: Optional[int] = None
    pad_multiple: Optional[int] = None
    csr: bool = False
    jumbo_threshold: int = 4096
    jumbo_pad_multiple: int = 2048
    hier_threshold: int = 1 << 16
    coarse_target: int = 8192
    refine_window: int = 8192

    def with_segment_padding(self) -> "ScaleConfig":
        """A copy whose ``pad_multiple`` defaults to ``segment``.

        A segmented decoder needs the padded node dim to divide into its
        segments; callers that build featurizer+simulator pairs from one
        ScaleConfig (``repro.api.place``, ``repro.hier``) normalize
        through this so the two always agree on the padded length."""
        if self.pad_multiple is not None or self.segment is None:
            return self
        return dataclasses.replace(self, pad_multiple=self.segment)

