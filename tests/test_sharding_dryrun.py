"""Sharding rules + a fast in-process dry-run on a small fake-device mesh.

The production 512-device lowering runs via ``repro/launch/dryrun.py``
(results cached in results/dryrun.json); here a subprocess with 16 fake
host devices lowers a reduced arch through the SAME sharding rules to keep
the rules regression-tested inside pytest.
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp
from repro.configs import get_reduced
from repro.models.model import build_model
from repro.dist import sharding as SH
mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_reduced("qwen3-8b")
model = build_model(cfg)
state_sh = jax.eval_shape(lambda: model.init_train_state(jax.random.PRNGKey(0)))
batch_sh = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
st = SH.state_specs(state_sh, mesh)
bt = SH.batch_specs(batch_sh, mesh)
with mesh:
    lowered = jax.jit(model.make_train_step(),
                      out_shardings=(SH.to_shardings(st, mesh), None)).lower(
        SH.with_shardings(state_sh, st, mesh),
        SH.with_shardings(batch_sh, bt, mesh))
    compiled = lowered.compile()
from repro.launch.hlo_analysis import peak_memory_bytes
print("PEAK", peak_memory_bytes(compiled.memory_analysis()))
from repro.launch.hlo_analysis import analyze_hlo
r = analyze_hlo(compiled.as_text())
print("COLL", r["collective_bytes"])
print("FLOPS", r["flops"])
"""


@pytest.mark.slow
def test_reduced_dryrun_on_16_fake_devices():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SUBPROC], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = dict(l.split(" ", 1) for l in out.stdout.strip().splitlines()
                 if " " in l)
    assert int(lines["PEAK"]) > 0
    assert float(lines["FLOPS"]) > 0
    assert float(lines["COLL"]) > 0      # FSDP/TP must communicate


def test_param_specs_cover_tree():
    """Every param leaf gets a PartitionSpec of matching rank."""
    import jax
    from jax.sharding import PartitionSpec
    from repro.configs import get_reduced
    from repro.models.model import build_model
    from repro.dist import sharding as SH
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for arch in ("jamba-1.5-large-398b", "deepseek-moe-16b", "whisper-base",
                 "xlstm-125m"):
        model = build_model(get_reduced(arch))
        shapes = model.param_shapes()
        specs = SH.param_specs(shapes, mesh)
        def check(sh, sp):
            assert isinstance(sp, PartitionSpec)
            assert len(sp) <= sh.ndim
        jax.tree_util.tree_map(check, shapes, specs,
                               is_leaf=lambda x: isinstance(x, PartitionSpec))


def test_dryrun_results_green_if_present():
    """If the full 512-device sweep has produced results, require them green."""
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun.json")
    if not os.path.exists(path):
        pytest.skip("full dry-run sweep not executed in this environment")
    rows = json.load(open(path))
    errors = {k: v.get("error") for k, v in rows.items()
              if v.get("status") == "error"}
    assert not errors, f"dry-run failures: {errors}"
    ok = [v for v in rows.values() if v.get("status") == "ok"]
    assert len(ok) >= 32
    for v in ok:
        peak = v["bytes_per_device"]["peak"]
        assert peak < 16e9, f"{v['arch']}|{v['shape']}|{v['mesh']}: {peak/1e9:.1f}GB > HBM"
