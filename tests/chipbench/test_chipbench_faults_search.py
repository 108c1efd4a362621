"""A run of the search cell, at a small size on the CPU and with the look
for a chip skipped, comes out ``correct`` as it stands and not correct
with the timed path broken underneath: a step that returns its state
unchanged, half of the batch left out of the update (the mean taken over
the rest), a makespan altered where the simulator produces it."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

sys.argv = sys.argv[:1]
import importlib.util  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "chipbench_run", os.path.join(ROOT, "chipbench", "run.py"))
run_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run_mod)

PEAKS = {"devices": {"cpu": {"bf16_flops": 1e12}}}


def small_config():
    cfg = harness.load_json(os.path.join(ROOT, "chipbench", "configs",
                                         "gnmt8-p100x8.json"))
    cfg["graph"]["kwargs"] = {"layers": 2, "time_steps": 5}
    cfg["policy"].update(hidden=32, ffn=64, window=16, segment=128,
                         gnn_chunk=64)
    return cfg


def _unchanged(monkeypatch):
    from repro.core import ppo
    real = ppo._update_fn

    def update(params, opt_state, *a, **k):
        _, _, aux = real(params, opt_state, *a, **k)
        return params, opt_state, aux
    monkeypatch.setattr(ppo, "_update_fn", update)


def _half_batch(monkeypatch):
    from repro.core import ppo
    real = ppo._update_fn

    def update(params, opt_state, pcfg, ocfg, gb, nd, placements, old_logp,
               adv, *rest):
        h = placements.shape[0] // 2
        return real(params, opt_state, pcfg, ocfg, gb, nd, placements[:h],
                    old_logp[:h], adv[:h], *rest)
    monkeypatch.setattr(ppo, "_update_fn", update)


def _altered_makespan(monkeypatch):
    from repro.sim import scheduler
    real = scheduler._simulate_batch_jit

    def sim(*a, **k):
        mk, r, valid = real(*a, **k)
        return mk.at[0].multiply(1.01), r, valid
    monkeypatch.setattr(scheduler, "_simulate_batch_jit", sim)


@pytest.mark.parametrize("fault", [None, _unchanged, _half_batch,
                                   _altered_makespan])
def test_correct_reads_each_fault(fault, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    result, checks = run_mod.run_cell("gnmt8-finetune", 2 ** 33 + 17, 0.5,
                                      False, require_chip=False,
                                      config=small_config(), peaks=PEAKS)
    failed = [c.name for c in checks if not c.ok]
    assert result["correct"] is (fault is None), failed
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"setup_s", "ppo_iter_s"}
