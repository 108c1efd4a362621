"""The benchmark's window arithmetic and its refusals: latency from the
due time, failures as misses, a rate over the whole window, and no
result without a chip or outside a checkout."""
import math
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench.drivers import serve  # noqa: E402


def _req(done_t, source="zero_shot", makespan=1.0):
    return SimpleNamespace(done_t=done_t, source=source, makespan=makespan)


def test_latency_runs_from_the_due_time_and_failures_miss():
    reqs = [(0.0, "f", {}), (1.0, "f", {}), (2.0, "f", {}), (3.0, "f", {}),
            (4.0, "f", {})]
    done = [_req(100.5), _req(101.25), _req(None), _req(103.5, "shed"),
            _req(109.0)]                   # the last answered after the close
    lat, failed, in_window = serve.summarize(reqs, done, 100.0, 5.0)
    assert lat[:2] == [0.5, 0.25]
    assert math.isinf(lat[2]) and math.isinf(lat[3])
    assert lat[4] == 5.0
    assert failed == 2
    assert in_window == 2                 # a rate over the whole window
    assert math.isinf(harness.percentile(lat, 95))
    assert harness.percentile(lat, 50) == 5.0


def test_latency_metrics_take_the_percentile_from_the_name():
    lat = [0.001 * i for i in range(1, 101)]
    out = serve.latency_metrics(["setup_s", "serve_p50_ms", "serve_p92_ms"],
                                lat)
    assert set(out) == {"serve_p50_ms", "serve_p92_ms"}
    assert math.isclose(out["serve_p50_ms"], 50.5)
    assert math.isclose(out["serve_p92_ms"], 92.08)
    assert math.isinf(serve.latency_metrics(
        ["serve_p92_ms"], lat[:90] + [float("inf")] * 10)["serve_p92_ms"])


def test_a_request_never_submitted_counts_as_failed():
    lat, failed, _ = serve.summarize([(0.0, "f", {}), (1.0, "f", {})],
                                     [_req(0.1)], 0.0, 2.0)
    assert failed == 1 and math.isinf(lat[1])


def test_checks_hold_values_to_their_limits():
    assert harness.Check("a", 0.5, 1.0).ok
    assert not harness.Check("a", 2.0, 1.0).ok
    assert not harness.Check("a", float("nan"), 1.0).ok
    assert harness.Check("n", 0, 0).ok


def test_seeds_wider_than_32_bits_give_31_bit_subseeds():
    a = harness.sub_seeds(2 ** 31 + 12345, 3)
    assert a == harness.sub_seeds(2 ** 31 + 12345, 3)
    assert a != harness.sub_seeds(2 ** 31 + 12346, 3)
    assert all(0 <= s < 2 ** 31 for s in a) and len(set(a)) == 3


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "gnmt8-finetune",
         "--seed", "0", "--seconds", "10", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_result_without_a_tpu():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert "platform='cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_no_result_in_a_directory_of_only_the_benchmark(tmp_path):
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
