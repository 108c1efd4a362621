"""The control of ``correct``: the plain reference with float8 inputs to
its matrix products, put in the program's place, comes out not correct against the limits the
configurations state, at a small size on the CPU (on the chip, at the
cells' own sizes, ``chipbench/calibrate.py`` reads it)."""
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.drivers import ppo, serve  # noqa: E402


def test_search_control_is_not_correct():
    cfg = harness.load_json(os.path.join(ROOT, "chipbench", "configs",
                                         "gnmt8-p100x8.json"))
    cfg["graph"]["kwargs"] = {"layers": 2, "time_steps": 5}
    cfg["policy"].update(hidden=32, ffn=64, window=16, segment=128,
                         gnn_chunk=64)
    objs, w0, prog, steps = ppo.checked_steps(cfg, 2 ** 32 + 99)
    g, caps = objs[0], objs[1]
    leaves = jax.tree_util.tree_leaves(w0)
    lim = cfg["correct"]["limits"]
    ref32 = ppo.reference_steps(g, caps, cfg, w0, steps)
    ref8 = ppo.reference_steps(g, caps, cfg, w0, steps, "float8")
    sound = ppo.compare(prog, ref32, leaves, lim)
    control = ppo.compare(ref8, ref32, leaves, lim)
    assert all(c.ok for c in sound), [(c.name, c.value) for c in sound]
    assert not all(c.ok for c in control), [(c.name, c.value)
                                            for c in control]


def test_serving_control_is_not_correct():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chipbench_faults_serve_mix", os.path.join(
            os.path.dirname(__file__), "test_chipbench_faults_serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = mod.small_config()
    cell = {"name": "table1-serve-miss", "config": cfg,
            "traffic": mod.small_mix(), "seed": 2 ** 35 + 1, "seconds": 2.0,
            "trace": False, "t_start": 0.0, "devices": jax.devices()[:1],
            "layers": {}, "peak_flops": None, "control": True,
            "metrics": ["serve_p50_ms"]}
    out = serve.run(cell)
    assert all(c.ok for c in out["checks"])
    limit = cfg["correct"]["limits"]["logp_gap"]
    assert out["control"]["logp_gap"] > limit, out["control"]
