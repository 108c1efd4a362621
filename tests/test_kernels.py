"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Shape/dtype sweeps per kernel as required: every sweep cell asserts
allclose against the ref.py oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention
from repro.kernels.segment_maxpool import neighbor_maxpool_dense
from repro.kernels import ops
from repro.core.scale import ScaleConfig

RNG = np.random.RandomState(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,sq,sk,d,causal,window", [
    (2, 128, 128, 64, True, None),
    (1, 256, 256, 32, True, 64),
    (3, 128, 256, 64, False, None),
    (2, 256, 128, 128, True, None),
    (1, 128, 128, 16, True, 32),
])
def test_flash_attention_sweep(bh, sq, sk, d, causal, window, dtype):
    q = jnp.asarray(RNG.randn(bh, sq, d), dtype)
    k = jnp.asarray(RNG.randn(bh, sk, d), dtype)
    v = jnp.asarray(RNG.randn(bh, sk, d), dtype)
    qo = sk - sq if (causal and sk > sq) else 0
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=qo, interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=causal, window=window,
                                q_offset=qo)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,m,h,density", [
    (64, 128, 128, 0.1),
    (128, 256, 128, 0.03),
    (64, 128, 256, 0.5),
    (128, 128, 128, 0.0),     # fully isolated rows
])
def test_maxpool_sweep(n, m, h, density, dtype):
    z = jnp.asarray(RNG.randn(m, h), dtype)
    adj = jnp.asarray(RNG.rand(n, m) < density)
    out = neighbor_maxpool_dense(z, adj, interpret=True)
    ref = R.neighbor_maxpool_ref(z, adj)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-5 if dtype == jnp.float32 else 5e-2)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10 ** 6), st.sampled_from([64, 128]),
       st.sampled_from([128, 256]))
def test_maxpool_property(seed, n, h):
    rng = np.random.RandomState(seed)
    z = jnp.asarray(rng.randn(n, h), jnp.float32)
    adj = jnp.asarray(rng.rand(n, n) < 0.15)
    out = neighbor_maxpool_dense(z, adj, interpret=True)
    ref = R.neighbor_maxpool_ref(z, adj)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ops_neighbor_maxpool_matches_gnn_path():
    """kernels.ops wrapper == padded-neighbor-list oracle == gnn jnp path."""
    n, h, k = 50, 64, 6
    rng = np.random.RandomState(3)
    z = jnp.asarray(rng.randn(n, h), jnp.float32)
    idx = jnp.asarray(rng.randint(0, n + 1, (n, k)), jnp.int32)
    mask = jnp.asarray((np.asarray(idx) < n) & (rng.rand(n, k) < 0.8),
                       jnp.float32)
    idx = jnp.where(mask > 0, idx, n)
    out = ops.neighbor_maxpool(z, idx, mask)
    ref = R.neighbor_maxpool_from_lists_ref(z, idx, mask)
    ref = jnp.where(ref <= -5e8, 0.0, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_gnn_pallas_agg_matches_jnp():
    from repro.core import gnn
    from repro.core.featurize import featurize
    from repro.graphs import synthetic as S
    g = S.rnnlm(2, time_steps=3)
    gb = featurize(g, max_deg=8)
    params = gnn.init(jax.random.PRNGKey(0), 32, 2)
    h_jnp = gnn.apply(params, gb, agg_impl="jnp")
    h_pl = gnn.apply(params, gb, agg_impl="pallas")
    np.testing.assert_allclose(np.asarray(h_jnp), np.asarray(h_pl),
                               atol=2e-5, rtol=1e-4)


# ===================================================================
# Block-sparse band attention (kernels/band_attention.py)
# ===================================================================

def test_band_attention_matches_ref_basic():
    """Direct kernel-vs-oracle on an exact-block shape, incl. a dynamic
    kv_lo (first-segment memory masking)."""
    from repro.kernels.band_attention import band_attention
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 64, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 64, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 64, 8), jnp.float32)
    for kv_lo in (0, 9, 31):
        out = band_attention(q, k, v, jnp.int32(kv_lo), diag_lo=0,
                             diag_hi=15, kv_len=64, block_q=32, block_k=32,
                             interpret=True)
        ref = R.band_attention_ref(q, k, v, diag_lo=0, diag_hi=15,
                                   kv_lo=kv_lo)
        # rows whose whole band is masked are unspecified by the kernel
        rows = np.arange(64)
        valid = (rows + 15) >= kv_lo
        np.testing.assert_allclose(np.asarray(out)[:, valid],
                                   np.asarray(ref)[:, valid], atol=2e-5)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10 ** 6), st.sampled_from([33, 70, 130]),
       st.sampled_from([0, 8, 32]))
def test_causal_window_band_property(seed, s, window):
    """ops.causal_window_attention(impl='band') == dense oracle at
    non-block-multiple lengths (padding handled by the wrapper)."""
    rng = np.random.RandomState(seed)
    w = window or None
    q = jnp.asarray(rng.randn(2, s, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, s, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, s, 8), jnp.float32)
    out = ops.causal_window_attention(q, k, v, window=w, impl="band")
    ref = R.flash_attention_ref(q, k, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10 ** 6), st.sampled_from([5, 16, 33]),
       st.sampled_from([4, 8, 16]), st.sampled_from([0, 3, 40]))
def test_band_memory_property(seed, s, window, base):
    """ops.band_mha_with_memory == the gather oracle of placer._tf_segment
    (memory cols before the start of time masked via dynamic kv_lo)."""
    rng = np.random.RandomState(seed)
    heads, hd = 2, 8
    wm1 = window - 1
    q = jnp.asarray(rng.randn(s, heads, hd), jnp.float32)
    kbuf = jnp.asarray(rng.randn(wm1 + s, heads, hd), jnp.float32)
    vbuf = jnp.asarray(rng.randn(wm1 + s, heads, hd), jnp.float32)
    out = ops.band_mha_with_memory(q, kbuf, vbuf, jnp.int32(base),
                                   window=window)
    idx = np.arange(s)[:, None] + np.arange(window)[None, :]
    valid = (base + idx - wm1) >= 0
    kb, vb = kbuf[idx], vbuf[idx]
    sc = jnp.einsum("nhd,nwhd->nhw", q, kb) / np.sqrt(np.float32(hd))
    sc = jnp.where(jnp.asarray(valid)[:, None, :], sc, -1e9)
    ref = jnp.einsum("nhw,nwhd->nhd", jax.nn.softmax(sc, axis=-1), vb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_band_kv_blocks_trip_counts():
    """The roofline's modeled trip count obeys the kernel's bounds: dense
    band == all blocks, narrow band strictly fewer, monotone in width,
    kv_len prunes trailing blocks."""
    from repro.kernels.band_attention import band_kv_blocks
    dense = band_kv_blocks(256, 256, diag_lo=-256, diag_hi=256,
                           block_q=64, block_k=64)
    assert dense == (256 // 64) * (256 // 64)
    narrow = band_kv_blocks(256, 256, diag_lo=-7, diag_hi=0,
                            block_q=64, block_k=64)
    assert narrow < dense
    prev = 0
    for w in (1, 8, 64, 256):
        b = band_kv_blocks(256, 256, diag_lo=-(w - 1), diag_hi=0,
                           block_q=64, block_k=64)
        assert b >= prev
        prev = b
    assert band_kv_blocks(256, 256, diag_lo=-256, diag_hi=256, kv_len=65,
                          block_q=64, block_k=64) == 4 * 2


# ===================================================================
# Padding regressions (fixed alongside the band kernel):
# non-block-multiple lengths used to leak padded keys / assert
# ===================================================================

@pytest.mark.parametrize("impl", ["flash", "band"])
@pytest.mark.parametrize("t", [70, 130])
def test_mha_with_memory_non_multiple_kv(impl, t):
    """mha_with_memory at T % block != 0: the zero-padded keys appended by
    the wrapper must NOT enter the softmax (they did before kv_len)."""
    rng = np.random.RandomState(11)
    s, heads, hd = 10, 2, 8
    q = jnp.asarray(rng.randn(s, heads, hd), jnp.float32)
    k = jnp.asarray(rng.randn(t, heads, hd), jnp.float32)
    v = jnp.asarray(rng.randn(t, heads, hd), jnp.float32)
    ones_q, ones_kv = jnp.ones(s), jnp.ones(t)
    out = ops.mha_with_memory(q, k, v, ones_q, ones_kv, impl=impl)
    sc = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(np.float32(hd))
    ref = jnp.einsum("hst,thd->shd", jax.nn.softmax(sc, axis=-1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("impl", ["flash", "band"])
def test_causal_window_attention_non_multiple_len(impl):
    """S=130 used to trip the block-divisibility assert; the wrapper now
    pads and masks, matching the oracle exactly."""
    rng = np.random.RandomState(12)
    q = jnp.asarray(rng.randn(2, 130, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 130, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 130, 8), jnp.float32)
    out = ops.causal_window_attention(q, k, v, window=32, impl=impl)
    ref = R.flash_attention_ref(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_kv_len_masks_padded_keys():
    """Direct kernel check of the kv_len fix: padded K/V columns past the
    real length change nothing."""
    rng = np.random.RandomState(13)
    q = jnp.asarray(rng.randn(1, 128, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 8), jnp.float32)
    kp = jnp.pad(k, ((0, 0), (0, 128), (0, 0)),
                 constant_values=7.0)          # poison the padding
    vp = jnp.pad(v, ((0, 0), (0, 128), (0, 0)), constant_values=7.0)
    out = flash_attention(q, kp, vp, causal=False, kv_len=128,
                          interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ===================================================================
# CSR-blocked neighbor max-pool (kernels/csr_maxpool.py)
# ===================================================================

@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10 ** 6), st.sampled_from([17, 60, 131]),
       st.sampled_from([1, 4, 8]))
def test_csr_maxpool_property(seed, n, deg):
    """CSR kernel == padded-neighbor-list oracle over fuzzed shapes
    (non-multiple row counts, forced empty-neighbor rows, isolates)."""
    rng = np.random.RandomState(seed)
    from repro.kernels.csr_maxpool import build_block_index
    z = jnp.asarray(rng.randn(n, 24), jnp.float32)
    idx = rng.randint(0, n + 1, (n, deg)).astype(np.int32)
    mask = ((idx < n) & (rng.rand(n, deg) < 0.7)).astype(np.float32)
    mask[n // 3: n // 2] = 0.0                 # empty-neighbor rows
    idx = np.where(mask > 0, idx, n)
    blocks = build_block_index(idx, mask, n, block_n=16, block_m=32)
    out = ops.neighbor_maxpool_csr(z, blocks, num_rows=n)
    agg = R.neighbor_maxpool_from_lists_ref(z, jnp.asarray(idx),
                                            jnp.asarray(mask))
    ref = jnp.where(agg <= -5e8, 0.0, agg)    # isolates zeroed, like ops
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    assert np.all(np.asarray(out)[n // 3: n // 2] == 0.0)


def test_csr_block_index_edge_cases():
    """Fully isolated graph -> zero non-empty tiles and an all-zero pool;
    sentinel-only rows never materialize adjacency."""
    from repro.kernels.csr_maxpool import build_block_index, nnz_blocks
    n = 40
    idx = np.full((n, 4), n, np.int32)
    mask = np.zeros((n, 4), np.float32)
    blocks = build_block_index(idx, mask, n, block_n=16, block_m=32)
    assert nnz_blocks(blocks) == 0
    z = jnp.asarray(np.random.RandomState(0).randn(n, 16), jnp.float32)
    out = ops.neighbor_maxpool_csr(z, blocks, num_rows=n)
    assert out.shape == (n, 16)
    assert np.all(np.asarray(out) == 0.0)

    # one edge -> exactly one non-empty tile, exact value through the pool
    idx2 = np.full((n, 4), n, np.int32)
    mask2 = np.zeros((n, 4), np.float32)
    idx2[5, 0], mask2[5, 0] = 17, 1.0
    blocks2 = build_block_index(idx2, mask2, n, block_n=16, block_m=32)
    assert nnz_blocks(blocks2) == 1
    out2 = ops.neighbor_maxpool_csr(z, blocks2, num_rows=n)
    np.testing.assert_array_equal(np.asarray(out2[5]), np.asarray(z[17]))


def test_csr_block_index_matches_dense_nnz():
    """The BSR index marks exactly the tiles the dense adjacency
    populates (no dropped and no phantom tiles)."""
    from repro.kernels.csr_maxpool import build_block_index
    rng = np.random.RandomState(21)
    n, deg, bn, bm = 60, 4, 16, 32
    idx = rng.randint(0, n + 1, (n, deg)).astype(np.int32)
    mask = ((idx < n) & (rng.rand(n, deg) < 0.6)).astype(np.float32)
    idx = np.where(mask > 0, idx, n)
    blocks = build_block_index(idx, mask, n, block_n=bn, block_m=bm)
    dense = np.zeros((blocks.adj.shape[0] * bn,
                      ((n + bm - 1) // bm) * bm), bool)
    for i in range(n):
        for j, m in zip(idx[i], mask[i]):
            if m > 0:
                dense[i, j] = True
    for r in range(blocks.col_blocks.shape[0]):
        want = {c for c in range(dense.shape[1] // bm)
                if dense[r * bn:(r + 1) * bn, c * bm:(c + 1) * bm].any()}
        got = {int(c) for c in np.asarray(blocks.col_blocks[r]) if c >= 0}
        assert got == want
        for c in got:
            np.testing.assert_array_equal(
                np.asarray(blocks.adj[r, list(np.asarray(
                    blocks.col_blocks[r])).index(c)]),
                dense[r * bn:(r + 1) * bn, c * bm:(c + 1) * bm])


# ===================================================================
# Framework routing: gnn / placer / policy behind the config flags
# ===================================================================

def test_gnn_pallas_csr_matches_jnp():
    from repro.core import gnn
    from repro.core.featurize import featurize
    from repro.graphs import synthetic as S
    g = S.rnnlm(2, time_steps=3)
    gb = featurize(g, max_deg=8, scale=ScaleConfig(csr=True))
    assert gb.csr_blocks is not None
    params = gnn.init(jax.random.PRNGKey(0), 32, 2)
    h_jnp = gnn.apply(params, gb, agg_impl="jnp")
    h_csr = gnn.apply(params, gb, agg_impl="pallas_csr")
    np.testing.assert_allclose(np.asarray(h_jnp), np.asarray(h_csr),
                               atol=2e-5, rtol=1e-4)


def test_gnn_pallas_csr_requires_block_index():
    """agg_impl='pallas_csr' without a scale.csr=True batch is a
    loud config error, not a silent fallback."""
    from repro.core import gnn
    from repro.core.featurize import featurize
    from repro.graphs import synthetic as S
    gb = featurize(S.rnnlm(2, time_steps=3), max_deg=8)
    params = gnn.init(jax.random.PRNGKey(0), 32, 2)
    with pytest.raises(ValueError, match="csr"):
        gnn.apply(params, gb, agg_impl="pallas_csr")


@pytest.mark.parametrize("fleet", ["uniform", "hetero"])
@pytest.mark.parametrize("segment", [None, 16])
def test_policy_kernel_impls_logp_parity(fleet, segment):
    """End-to-end tolerance pin: logp under attn_impl='pallas_band' +
    agg_impl='pallas_csr' matches the golden-pinned jnp defaults across
    monolithic/segmented x uniform/hetero fleets."""
    import dataclasses
    from repro.core import policy as P
    from repro.core.featurize import featurize
    from repro.core.policy import PolicyConfig
    from repro.graphs import synthetic as S
    from repro.sim import p100_topology
    from repro.sim.device import multi_gen_fleet
    g = S.rnnlm(2, time_steps=3)
    topo = (p100_topology(4).with_mem_caps(g.total_mem())
            if fleet == "uniform"
            else multi_gen_fleet().tightened(g.total_mem()))
    gb = featurize(g, max_deg=8, topo=topo, scale=ScaleConfig(csr=True))
    cfg = PolicyConfig(hidden=32, gnn_layers=2, placer_layers=2, ffn=64,
                       window=32, max_devices=8,
                       scale=ScaleConfig(segment=segment))
    cfg_k = dataclasses.replace(cfg, attn_impl="pallas_band",
                                agg_impl="pallas_csr")
    params = P.init(jax.random.PRNGKey(0), cfg)
    pl_s, _ = P.sample(params, cfg, gb, topo.num_devices,
                       jax.random.PRNGKey(1), 2)
    lp_ref, ent_ref = P.logp_and_entropy(params, cfg, gb,
                                         topo.num_devices, pl_s)
    lp_krn, ent_krn = P.logp_and_entropy(params, cfg_k, gb,
                                         topo.num_devices, pl_s)
    np.testing.assert_allclose(np.asarray(lp_krn), np.asarray(lp_ref),
                               atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(float(ent_krn), float(ent_ref), atol=5e-5)


# ===================================================================
# hypothesis fallback shim (the only provider of @given in the image)
# ===================================================================

def test_hypothesis_fallback_shim_contract():
    """The shim behind this file's @given tests: deterministic example
    streams within the declared strategy domains, max_examples honored,
    install() registers importable modules."""
    from repro.testing import hypothesis_fallback as HF

    def run():
        calls = []

        @HF.settings(max_examples=7, deadline=None)
        @HF.given(HF.strategies.integers(0, 5),
                  HF.strategies.sampled_from(["a", "b"]))
        def fake(x, y):
            calls.append((x, y))

        fake()
        return calls

    first, second = run(), run()
    assert len(first) == 7
    assert first == second                     # fixed-seed determinism
    assert all(0 <= x <= 5 and y in ("a", "b") for x, y in first)

    mods = {}
    HF.install(mods)
    assert mods["hypothesis"].strategies.integers is HF.integers
    assert mods["hypothesis.strategies"].sampled_from is HF.sampled_from


# ===================================================================
# Gradients: kernel forward, oracle backward (custom_vjp)
# ===================================================================

def test_band_attention_grad_matches_oracle():
    """d/d(q,k,v) through ops.causal_window_attention(impl='band') ==
    the dense oracle's gradients (pallas has no JVP; the wrapper's
    custom_vjp differentiates the jnp oracle instead)."""
    rng = np.random.RandomState(17)
    q = jnp.asarray(rng.randn(2, 70, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 70, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 70, 8), jnp.float32)
    ct = jnp.asarray(rng.randn(2, 70, 8), jnp.float32)

    def f_kernel(q, k, v):
        return (ops.causal_window_attention(q, k, v, window=16,
                                            impl="band") * ct).sum()

    def f_ref(q, k, v):
        return (R.flash_attention_ref(q, k, v, causal=True,
                                      window=16) * ct).sum()

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_csr_maxpool_grad_matches_oracle():
    """dz through ops.neighbor_maxpool_csr routes the cotangent to the
    argmax entries exactly like the padded-list oracle."""
    from repro.kernels.csr_maxpool import build_block_index
    rng = np.random.RandomState(19)
    n, deg = 60, 4
    z = jnp.asarray(rng.randn(n, 24), jnp.float32)
    idx = rng.randint(0, n + 1, (n, deg)).astype(np.int32)
    mask = ((idx < n) & (rng.rand(n, deg) < 0.7)).astype(np.float32)
    idx = np.where(mask > 0, idx, n)
    blocks = build_block_index(idx, mask, n, block_n=16, block_m=32)
    ct = jnp.asarray(rng.randn(n, 24), jnp.float32)

    def f_kernel(z):
        return (ops.neighbor_maxpool_csr(z, blocks, num_rows=n) * ct).sum()

    def f_ref(z):
        agg = R.neighbor_maxpool_from_lists_ref(z, jnp.asarray(idx),
                                                jnp.asarray(mask))
        return (jnp.where(agg <= -5e8, 0.0, agg) * ct).sum()

    gk = jax.grad(f_kernel)(z)
    gr = jax.grad(f_ref)(z)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-6)


def test_csr_blocks_ref_matches_lists_ref():
    """The BSR-form oracle (the custom_vjp backward) agrees with the
    padded-list oracle on the raw NEG contract."""
    from repro.kernels.csr_maxpool import build_block_index
    rng = np.random.RandomState(23)
    n, deg = 50, 5
    z = jnp.asarray(rng.randn(n, 16), jnp.float32)
    idx = rng.randint(0, n + 1, (n, deg)).astype(np.int32)
    mask = ((idx < n) & (rng.rand(n, deg) < 0.6)).astype(np.float32)
    idx = np.where(mask > 0, idx, n)
    blocks = build_block_index(idx, mask, n, block_n=16, block_m=32)
    got = R.csr_maxpool_blocks_ref(z, blocks.col_blocks, blocks.adj)[:n]
    want = R.neighbor_maxpool_from_lists_ref(z, jnp.asarray(idx),
                                             jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_ppo_iteration_with_kernel_impls():
    """Regression: a PPO update (value_and_grad through logp_and_entropy)
    with attn_impl='pallas_band' + agg_impl='pallas_csr' used to crash in
    pallas_call's missing JVP rule; it must train end-to-end."""
    import dataclasses
    from benchmarks import common as C
    from repro.core.featurize import featurize
    from repro.core.policy import PolicyConfig
    from repro.core.ppo import PPOConfig, PPOTrainer
    from repro.graphs import synthetic as S
    g = S.rnnlm(2, time_steps=3)
    task = C.make_task("kern-ppo", g, 4, segment=16)
    gb = featurize(g, max_deg=8, topo=task.topo,
                   scale=ScaleConfig(pad_multiple=16, csr=True))
    pcfg = PolicyConfig(hidden=32, gnn_layers=2, placer_layers=2, ffn=64,
                        window=32, max_devices=8,
                        scale=ScaleConfig(segment=16, gnn_chunk=16),
                        attn_impl="pallas_band", agg_impl="pallas_csr")
    tr = PPOTrainer(pcfg, PPOConfig(num_samples=4, epochs=1), seed=0)
    m = tr.iteration(task.name, gb, task.env, task.num_devices)
    assert np.isfinite(m["best_makespan"])
    assert m["best_placement"] is not None
