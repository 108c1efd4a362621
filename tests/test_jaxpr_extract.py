"""jaxpr extraction: real JAX computations -> GDP-placeable graphs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import list_archs
from repro.core.featurize import featurize
from repro.core.graph import op_id
from repro.graphs.jaxpr_extract import extract, extract_arch
from repro.sim import p100_topology, prepare_sim_graph
from repro.sim.scheduler import Env


def test_extract_mlp_with_scan():
    def mlp(x, w1, w2):
        h = jax.nn.relu(x @ w1)
        def body(c, _):
            return jnp.tanh(c @ w2), None
        h, _ = jax.lax.scan(body, h, None, length=4)
        return jnp.sum(h)

    x = jnp.zeros((8, 64))
    w1 = jnp.zeros((64, 128))
    w2 = jnp.zeros((128, 128))
    g = extract(mlp, x, w1, w2, name="mlp")
    g.validate()
    assert g.num_nodes >= 5
    # scan body flops counted x4 trips
    scan_flops = 4 * 2 * 8 * 128 * 128
    assert g.total_flops() >= scan_flops


def test_extract_grad_graph_larger():
    def loss(x, w):
        return jnp.sum(jnp.tanh(x @ w))
    x, w = jnp.zeros((4, 8)), jnp.zeros((8, 8))
    g_f = extract(loss, x, w, name="f")
    g_b = extract(lambda x, w: jax.grad(loss, argnums=1)(x, w).sum(),
                  x, w, name="b")
    assert g_b.num_nodes > g_f.num_nodes


def test_extracted_model_zoo_graph_placeable():
    """Reduced assigned-arch jaxpr -> GDP environment end to end."""
    from repro.configs import get_reduced
    from repro.models.model import build_model
    cfg = get_reduced("starcoder2-3b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    g = extract(model.loss, params, batch, name="starcoder2-reduced")
    g.validate()
    assert g.num_nodes > 20
    topo = p100_topology(2)
    env = Env(prepare_sim_graph(g, topo, max_deg=16), topo)
    gb = featurize(g, max_deg=8, topo=topo)
    rng = np.random.RandomState(0)
    pl = jnp.asarray(rng.randint(0, 2, (4, g.num_nodes)), jnp.int32)
    mk, r, valid = env.rewards(pl)
    assert np.all(np.asarray(mk) > 0)


# ---------------------------------------------------------------------------
# scan expansion (expand=) and the extract_arch disk cache
# ---------------------------------------------------------------------------
def _scan_mlp():
    def mlp(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        h, _ = jax.lax.scan(body, x, None, length=4)
        return jnp.sum(h)
    return mlp, jnp.zeros((8, 32)), jnp.zeros((32, 32))


def test_expand_unrolls_scan_and_conserves_flops():
    fn, x, w = _scan_mlp()
    fused = extract(fn, x, w, name="fused")
    big = extract(fn, x, w, name="big", expand=8)
    # 4 trips of (matmul, tanh) replace one opaque scan node
    assert big.num_nodes > fused.num_nodes
    np.testing.assert_allclose(big.total_flops(), fused.total_flops(),
                               rtol=1e-12)
    big.validate()
    # expand mode emits nodes in topological creation order
    assert np.all(big.src < big.dst)


def test_expand_longer_than_budget_stays_fused():
    fn, x, w = _scan_mlp()
    fused = extract(fn, x, w, name="fused")
    small = extract(fn, x, w, name="small", expand=2)   # length 4 > 2
    assert small.num_nodes == fused.num_nodes
    np.testing.assert_allclose(small.total_flops(), fused.total_flops())


def test_expand_none_is_bit_identical_to_legacy():
    fn, x, w = _scan_mlp()
    g1 = extract(fn, x, w, name="g")
    g2 = extract(fn, x, w, name="g", expand=None)
    for f in ("op_type", "flops", "out_bytes", "mem_bytes", "out_shape",
              "src", "dst"):
        assert np.array_equal(getattr(g1, f), getattr(g2, f)), f


def test_extract_arch_disk_cache_roundtrip(tmp_path):
    from repro.graphs.jaxpr_extract import extract_arch
    kw = dict(reduced=True, mode="loss", seq=16, batch=2,
              cache_dir=str(tmp_path))
    g1 = extract_arch("starcoder2-3b", **kw)
    cached = list(tmp_path.glob("*.npz"))
    assert len(cached) == 1 and ".tmp" not in cached[0].name
    g2 = extract_arch("starcoder2-3b", **kw)   # second call hits the cache
    for f in ("op_type", "flops", "out_bytes", "mem_bytes", "out_shape",
              "src", "dst"):
        assert np.array_equal(getattr(g1, f), getattr(g2, f)), f
    assert g1.name == g2.name


def test_extract_arch_digest_keys_config(tmp_path):
    from repro.graphs.jaxpr_extract import arch_digest
    base = arch_digest("qwen3-8b", mode="grad", seq=64, expand=8)
    assert arch_digest("qwen3-8b", mode="grad", seq=64, expand=8) == base
    assert arch_digest("qwen3-8b", mode="loss", seq=64, expand=8) != base
    assert arch_digest("qwen3-8b", mode="grad", seq=128, expand=8) != base
    assert arch_digest("qwen3-8b", mode="grad", seq=64, expand=16) != base


# ---------------------------------------------------------------------------
# the call primitive of the installed jax, and the model zoo's gradients
# ---------------------------------------------------------------------------
def test_jitted_sub_function_is_seen_through():
    """A ``jax.jit`` call inside the traced function is a call primitive
    like ``pjit`` was: fused, it costs its body's FLOPs; expanded, its
    matmul is a node of its own."""
    inner = jax.jit(lambda a, b: jnp.tanh(a @ b))

    def fn(x, w):
        return jnp.sum(inner(x, w))

    x, w = jnp.zeros((8, 32)), jnp.zeros((32, 16))
    mm = 2.0 * 8 * 32 * 16
    fused = extract(fn, x, w, name="fused")
    assert fused.total_flops() >= mm
    big = extract(fn, x, w, name="big", expand=4)
    is_mm = big.op_type == op_id("matmul")
    assert int(is_mm.sum()) == 1 and big.flops[is_mm][0] == mm
    np.testing.assert_allclose(big.total_flops(), fused.total_flops())


@pytest.mark.parametrize("arch", list_archs())
def test_grad_of_every_reduced_config_extracts(arch, tmp_path):
    g = extract_arch(arch, reduced=True, mode="grad", seq=32, batch=2,
                     cache_dir=str(tmp_path))
    g.validate()
    loss = extract_arch(arch, reduced=True, mode="loss", seq=32, batch=2,
                        cache_dir=str(tmp_path))
    assert g.num_nodes > loss.num_nodes
    assert int(g.op_type.max()) < 25          # featurize embeds 25 op ids


# (nodes, total FLOPs) of each reduced config's gradient graph, seq 32,
# batch 2, expand 8, pinned with the repaired extractor before the zoo's
# model code took Nemotron-H's layers; nemotron's own row pinned with it
PINNED = {
    "arctic-480b": (1355, 88810594.0),
    "deepseek-moe-16b": (1371, 85534184.0),
    "gemma2-9b": (1164, 51041976.0),
    "jamba-1.5-large-398b": (5600, 256222605.0),
    "mistral-large-123b": (986, 41710160.0),
    "qwen2-vl-7b": (1016, 42786752.0),
    "qwen3-8b": (1140, 41902416.0),
    "starcoder2-3b": (986, 41710160.0),
    "whisper-base": (3277, 9818466806.0),
    "xlstm-125m": (763, 44013407.0),
    "nemotron-3-nano-30b-a3b": (4093, 84319839.0),
}


def test_every_config_is_pinned():
    assert set(PINNED) == set(list_archs())


@pytest.mark.parametrize("arch", sorted(PINNED))
def test_reduced_grad_graph_keeps_its_pinned_size(arch, tmp_path):
    g = extract_arch(arch, reduced=True, mode="grad", seq=32, batch=2,
                     expand=8, cache_dir=str(tmp_path))
    nodes, flops = PINNED[arch]
    assert g.num_nodes == nodes
    assert g.total_flops() == pytest.approx(flops, rel=1e-9)
