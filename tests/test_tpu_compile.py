"""Compile-only checks of the Pallas kernels for a described TPU v5e.

The TPU compiler ships with jaxlib, so a ``v5e:2x2`` topology can be
described without a chip and programs compiled for its first device.
Nothing runs: these tests catch what interpret mode cannot — tiling rules,
unsupported vector shape casts, VMEM overruns — at the block shapes of the
50k-node GNMT-8 cell (``benchmarks/large_graph.large_policy()``: segment
512, window 64, hidden 64, 4 heads, 8 devices; CSR index 848 row blocks x
11 tiles of 64x128).  Each test asserts the kernel survived as a Mosaic
custom call (``tpu_custom_call``) in the compiled HLO.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import policy as P
from repro.kernels import ops
from repro.kernels.band_attention import band_attention
from repro.kernels.csr_maxpool import _csr_call

# GNMT-8 at time_steps=352: 53,909 nodes padded to 106 segments of 512
GNMT8_ROWS = 54_272
CSR_ROW_BLOCKS, CSR_TILES = GNMT8_ROWS // 64, 11


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it and out of the trace caches."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(lowered) -> str:
    return lowered.compile().as_text()


def test_band_attention_compiles_for_v5e(one_chip, no_cache):
    """Segmented TF band: q [heads, S, hd], K/V buffer padded past W-1+S."""
    q = _spec((4, 512, 16), jnp.float32, one_chip)
    kv = _spec((4, 640, 16), jnp.float32, one_chip)
    lo = _spec((), jnp.int32, one_chip)
    hlo = _hlo(band_attention.lower(q, kv, kv, lo, diag_lo=0, diag_hi=63,
                                    kv_len=575, block_q=128, block_k=128,
                                    interpret=False))
    assert "tpu_custom_call" in hlo


def test_csr_maxpool_compiles_for_v5e(one_chip, no_cache):
    """CSR max-pool over the whole GNMT-8 activation matrix: only the
    referenced [128, 64] feature tile may be resident in VMEM."""
    z = _spec((GNMT8_ROWS, 64), jnp.float32, one_chip)
    cb = _spec((CSR_ROW_BLOCKS, CSR_TILES), jnp.int32, one_chip)
    adj = _spec((CSR_ROW_BLOCKS, CSR_TILES, 64, 128), jnp.int8, one_chip)
    compiled = _csr_call.lower(z, cb, adj, block_h=128,
                               interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # output + inputs live in HBM; nothing graph-sized is staged twice
    assert mem.temp_size_in_bytes < 64 << 20, mem


def test_tf_segment_band_compiles_for_v5e(one_chip, no_cache, monkeypatch):
    """One jitted teacher-forced segment with attn_impl='pallas_band' at
    large_policy() shapes.  The kernel wrappers pick interpret mode from
    the default backend (the CPU here), so the test steers them."""
    from benchmarks.large_graph import large_policy
    from repro.core import placer as PL

    monkeypatch.setattr(ops, "interpret", lambda: False)
    cfg = large_policy()
    params = jax.eval_shape(lambda: P.init(jax.random.PRNGKey(0), cfg))
    pp = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, one_chip), params["placer"])
    s, hid, dmax = cfg.scale.segment, cfg.hidden, cfg.max_devices
    hd = hid // cfg.heads
    f32 = jnp.float32
    mem = _spec((cfg.placer_layers, cfg.window - 1, cfg.heads, hd), f32,
                one_chip)
    args = (pp, _spec((s, hid), f32, one_chip), mem, mem,
            _spec((s,), f32, one_chip), _spec((), jnp.int32, one_chip),
            _spec((hid,), f32, one_chip), _spec((dmax, hid), f32, one_chip),
            _spec((s, dmax), f32, one_chip), _spec((s,), f32, one_chip),
            _spec((dmax,), f32, one_chip), None)
    hlo = _hlo(PL._tf_segment.lower(*args, heads=cfg.heads,
                                    num_devices=dmax, use_attention=True,
                                    attn_impl="pallas_band"))
    assert "tpu_custom_call" in hlo
