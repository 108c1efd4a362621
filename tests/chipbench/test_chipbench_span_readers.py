"""The per-layer metrics that read device idle under the program's spans:
each reader on hand-made inputs, each silent where the program has
nothing for it, and a traced run of each cell, at a small size on the
CPU, that reports every per-layer metric of the cell."""
import importlib.util
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

IDLE = [["placer.tf_segment", 0.9], ["serve.fingerprint", 0.75],
        ["ppo.update.grad", 2.8], ["host", 1.2], ["ppo.update.optim", 0.2]]
INPUTS = {"trace": {"idle_gaps": IDLE}, "iterations": 2}
EMPTY = {"trace": {"idle_gaps": [["host", 2.2], ["serve.batch", 0.04],
                                 ["ppo.update", 1.4]]},
         "iterations": 1}

READINGS = [("fingerprint_idle_s.serve", 0.75),
            ("update_grad_idle_s.search", 1.4),
            ("update_optim_idle_s.search", 0.1)]


@pytest.mark.parametrize("name,want", READINGS)
def test_reader_on_hand_made_inputs(name, want):
    got = harness.load_module("metrics", name).read(INPUTS)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n, _ in READINGS])
def test_reader_finds_nothing_to_read(name):
    assert harness.load_module("metrics", name).read(EMPTY) is None


# idle by span as a traced chip run of each cell broke it down, on this
# program and on one without the new spans (device idle seconds)
CHIP_SERVE = [["host", 1.1738], ["serve.fingerprint", 0.7761],
              ["serve.batch", 0.1510], ["serve.baselines", 0.0726],
              ["serve.submit", 0.0529], ["serve.zero_shot", 0.0023]]
CHIP_SEARCH = [["ppo.update.grad", 1.3985], ["placer.tf_segment", 0.9371],
               ["ppo.sample", 0.1559], ["ppo.update.optim", 0.1036],
               ["ppo.logp", 0.0740], ["placer.ar_segment", 0.0073],
               ["host", 0.0065], ["ppo.simulate", 0.0012],
               ["ppo.relabel", 0.0005]]
CHIP_READINGS = [("fingerprint_idle_s.serve", 0.7761),
                 ("update_grad_idle_s.search", 1.3985),
                 ("update_optim_idle_s.search", 0.1036)]
OLD_PROGRAM = [["host", 2.2779], ["serve.batch", 0.0350],
               ["sim.rewards", 0.0034], ["ppo.update", 1.408],
               ["placer.tf_segment", 0.886], ["ppo.sample", 0.216]]


@pytest.mark.parametrize("name,want", CHIP_READINGS)
def test_reader_on_a_chip_breakdown(name, want):
    mod = harness.load_module("metrics", name)
    got = mod.read({"trace": {"idle_gaps": CHIP_SERVE + CHIP_SEARCH},
                    "iterations": 1})
    assert got == pytest.approx(want)
    old = mod.read({"trace": {"idle_gaps": OLD_PROGRAM}, "iterations": 1})
    assert old is None


@pytest.mark.parametrize("name,want", READINGS)
def test_reader_on_merged_captures(name, want):
    from chipbench import trace
    halves = [[[n, t / 2.0] for n, t in IDLE], [[n, t / 2.0] for n, t in IDLE]]
    reds = [{"busy_s": 1.0, "window_s": 2.0, "layer_s": {}, "device_ops": [],
             "idle_gaps": h} for h in halves]
    merged = trace.merge(reds)
    got = harness.load_module("metrics", name).read(
        {"trace": merged, "iterations": 2})
    assert got == pytest.approx(want)


def test_each_reader_has_its_benchmark_entry():
    bench = harness.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, _ in READINGS:
        m = entries[name]
        assert m["source"] == "program_span"
        cell = "table1-serve-miss" if name.endswith(".serve") else \
            "gnmt8-finetune"
        assert m["workloads"] == [cell]


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cell_metrics(bench, cell):
    return {m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", ["table1-serve-miss", "gnmt8-finetune"])
def test_traced_run_reports_every_per_layer_metric(cell):
    run_mod = _load("chipbench_run_traced", os.path.join(
        ROOT, "chipbench", "run.py"))
    here = os.path.dirname(__file__)
    if cell == "table1-serve-miss":
        faults = _load("chipbench_traced_serve", os.path.join(
            here, "test_chipbench_faults_serve.py"))
        kw = dict(config=faults.small_config(), mix=faults.small_mix())
        seconds = 2.0
    else:
        faults = _load("chipbench_traced_search", os.path.join(
            here, "test_chipbench_faults_search.py"))
        kw = dict(config=faults.small_config())
        seconds = 1.0
    result, checks = run_mod.run_cell(cell, 2 ** 33 + 5, seconds, True,
                                      require_chip=False, peaks=faults.PEAKS,
                                      **kw)
    assert result["correct"], [(c.name, c.value) for c in checks]
    got = set(result["metrics"])
    names = {n for n, _ in result["breakdown"]["idle_gaps"]}
    assert got == _cell_metrics(harness.benchmark(), cell), names
    assert "sim.rewards" not in names
