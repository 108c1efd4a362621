"""A run of the serving cell, at a small size on the CPU and with the
look for a chip skipped, comes out ``correct`` as it stands and not
correct with the timed path broken underneath: half of each batch left
out (its rows served from the other half), a device altered in the
placements where the decode produces them."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

import importlib.util  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "chipbench_run_serve", os.path.join(ROOT, "chipbench", "run.py"))
run_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run_mod)

PEAKS = {"devices": {"cpu": {"bf16_flops": 1e12}}}


def small_mix():
    """The mix with its revisions that fit the smallest bucket, its
    warm-up graph for that bucket, at 5 requests per second."""
    mix = harness.traffic_file("serve-miss")
    fams = [dict(f, revisions=[r for r in f["revisions"] if r["nodes"] <= 256])
            for f in mix["families"]]
    return dict(mix, families=fams, rate_rps=5.0,
                warmup=[w for w in mix["warmup"] if w["nodes"] <= 256])


def small_config():
    cfg = harness.config_file(harness.benchmark(), "table1-p100x4")
    cfg["policy"].update(hidden=32, ffn=64, window=16)
    cfg["correct"]["requests"] = 10
    return cfg


def _half_batch(monkeypatch):
    from repro.serve import service
    real = service._sample_batch_jit

    def sample(params, pcfg, sgb, *a, **k):
        # every odd row is left out and answered from the row before it
        import numpy as np
        keep = np.arange(sgb.op.shape[0]) // 2 * 2
        sgb = type(sgb)(*[x[keep] if hasattr(x, "ndim") and x.ndim else x
                          for x in sgb])
        return real(params, pcfg, sgb, *a, **k)
    monkeypatch.setattr(service, "_sample_batch_jit", sample)


def _altered_device(monkeypatch):
    from repro.serve import service
    real = service._sample_batch_jit

    def sample(*a, **k):
        pl, lp = real(*a, **k)
        return pl.at[:, :, 3].set((pl[:, :, 3] + 1) % 4), lp
    monkeypatch.setattr(service, "_sample_batch_jit", sample)


@pytest.mark.parametrize("fault", [None, _half_batch, _altered_device])
def test_correct_reads_each_fault(fault, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    result, checks = run_mod.run_cell("table1-serve-miss", 2 ** 34 + 3, 2.0,
                                      False, require_chip=False,
                                      config=small_config(), mix=small_mix(),
                                      peaks=PEAKS)
    failed = [c.name for c in checks if not c.ok]
    assert result["correct"] is (fault is None), failed
    assert result["attempted"] == 10
    assert set(result["metrics"]) == {"setup_s", "serve_p50_ms",
                                      "serve_p92_ms"}
