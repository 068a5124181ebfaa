"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import bitset
from repro.kernels import ops, ref, runtime


@pytest.mark.parametrize("b,n,w", [(1, 16, 1), (13, 100, 7), (32, 257, 4),
                                   (8, 128, 32)])
@pytest.mark.parametrize("block_b,block_n", [(8, 128), (4, 64)])
def test_frontier_expand(b, n, w, block_b, block_n):
    rng = np.random.default_rng(b * n + w)
    p = jnp.asarray(rng.integers(0, 2 ** 32, (b, w), dtype=np.uint32))
    ext = jnp.asarray(rng.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    out = ops.frontier_expand(p, ext, block_b=block_b, block_n=block_n)
    want = ref.frontier_expand_ref(p, ext)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ---------------------------------------------- masked-intersection kernel
# ragged on purpose: W=1, B/N not multiples of any block size
@pytest.mark.parametrize("b,n,w", [(1, 16, 1), (5, 257, 1), (13, 100, 7),
                                   (32, 300, 4), (7, 1, 2)])
@pytest.mark.parametrize("block_b,block_n", [(8, 128), (3, 37)])
@pytest.mark.parametrize("with_mask", [False, True])
def test_masked_intersect_matches_reference(b, n, w, block_b, block_n,
                                            with_mask):
    rng = np.random.default_rng(b * n * w + block_b)
    a = jnp.asarray(rng.integers(0, 2 ** 32, (b, w), dtype=np.uint32))
    cols = jnp.asarray(rng.integers(0, 2 ** 32, (n, w), dtype=np.uint32))
    mask = jnp.asarray(
        rng.integers(0, 2 ** 32, (b, w), dtype=np.uint32)) if with_mask \
        else None
    out = ops.masked_intersect(a, cols, mask, block_b=block_b,
                               block_n=block_n)
    want = ref.masked_intersect_ref(a, cols, mask)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_masked_intersect_membership_via_eye_table():
    """With one-hot columns the kernel is a batched membership probe:
    counts[r, v] = bit v of (a & mask)[r] (the iso candidate-grid case)."""
    rng = np.random.default_rng(7)
    n = 100
    a = jnp.asarray(rng.integers(0, 2 ** 32, (9, 4), dtype=np.uint32))
    mask = jnp.asarray(rng.integers(0, 2 ** 32, (9, 4), dtype=np.uint32))
    eye = jnp.asarray(bitset.eye_table(n))
    member = ops.masked_intersect(a, eye, mask) > 0
    want = np.asarray(bitset.to_bool(a & mask, n))
    np.testing.assert_array_equal(np.asarray(member), want)


def test_frontier_expand_is_maskless_specialization():
    rng = np.random.default_rng(11)
    p = jnp.asarray(rng.integers(0, 2 ** 32, (6, 3), dtype=np.uint32))
    ext = jnp.asarray(rng.integers(0, 2 ** 32, (40, 3), dtype=np.uint32))
    np.testing.assert_array_equal(
        np.asarray(ops.frontier_expand(p, ext)),
        np.asarray(ops.masked_intersect(p, ext)))


# ------------------------------------------------ interpret auto-detection
def test_interpret_autodetect(monkeypatch):
    """interpret=None must lower for real on TPU and interpret elsewhere;
    REPRO_PALLAS_COMPILE=1 forces real lowering (the old hardcoded
    interpret=True silently interpreted on TPU)."""
    monkeypatch.delenv("REPRO_PALLAS_COMPILE", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert runtime.default_interpret() is True
    assert runtime.resolve_interpret(None) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.default_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.setenv("REPRO_PALLAS_COMPILE", "1")
    assert runtime.default_interpret() is False
    # explicit values always win over detection
    assert runtime.resolve_interpret(True) is True
    assert runtime.resolve_interpret(False) is False


def test_masked_intersect_both_execution_paths():
    """Parity in interpret mode.  The compiled path is compiled for a
    described TPU in tests/test_tpu_compile.py and run on the chip by
    chip_smoke.py."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.integers(0, 2 ** 32, (13, 4), dtype=np.uint32))
    cols = jnp.asarray(rng.integers(0, 2 ** 32, (130, 4), dtype=np.uint32))
    mask = jnp.asarray(rng.integers(0, 2 ** 32, (13, 4), dtype=np.uint32))
    out = ops.masked_intersect(a, cols, mask, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.masked_intersect_ref(a, cols, mask)))


# ------------------------------------------- workload kernel-path parity
def _iso_run(g, index, use_pallas, cand_path="batched"):
    from repro.core.engine import Engine, EngineConfig
    from repro.core.iso import make_iso_computation
    comp = make_iso_computation(
        g, [(0, 1), (1, 2), (2, 3)], [0, 1, 0, 2], index,
        use_pallas=use_pallas, cand_path=cand_path)
    res = Engine(comp, EngineConfig(k=3, batch=32, pool_capacity=4096,
                                    max_steps=20000)).run()
    return (np.asarray(res.result_keys).tolist(),
            np.asarray(res.result_states).tolist(), res.candidates)


def test_iso_topk_identical_with_and_without_kernel():
    """Byte-identical top-k (keys AND states) across the per-state loop,
    batched-jnp, and Pallas candidate-generation paths."""
    from repro.core.iso import build_iso_index
    from repro.data.synthetic_graphs import labeled_graph
    g = labeled_graph(n=90, m=300, n_labels=3, seed=4)
    index = build_iso_index(g, max_hops=3)
    per_state = _iso_run(g, index, use_pallas=False, cand_path="map")
    vmapped = _iso_run(g, index, use_pallas=False, cand_path="vmap")
    batched = _iso_run(g, index, use_pallas=False)
    kernel = _iso_run(g, index, use_pallas=True)
    assert per_state == vmapped == batched == kernel


def test_weighted_clique_rejects_kernel_path():
    """weighted-clique needs a weighted-popcount kernel variant, so
    use_pallas must be rejected at validation, not silently ignored."""
    from repro.data.synthetic_graphs import planted_clique_graph
    from repro.service.api import (DiscoveryRequest, GraphRegistry,
                                   ValidationError)
    reg = GraphRegistry()
    reg.register("g", planted_clique_graph(30, 100, 5, seed=0))
    req = DiscoveryRequest(graph="g", workload="weighted-clique",
                           weights=tuple([1] * 30), use_pallas=True)
    with pytest.raises(ValidationError, match="weighted-clique"):
        req.validate(reg)
    # and without the knob it still validates fine
    DiscoveryRequest(graph="g", workload="weighted-clique",
                     weights=tuple([1] * 30)).validate(reg)


def test_pattern_topk_identical_with_and_without_kernel():
    """Mining with kernel edge probes returns the identical pattern list,
    supports, and candidate count as the numpy reference path."""
    from repro.core.aggregate import topk_frequent_patterns
    from repro.data.synthetic_graphs import labeled_graph
    g = labeled_graph(n=60, m=180, n_labels=3, seed=9)
    a = topk_frequent_patterns(g, m_edges=3, k=3)
    b = topk_frequent_patterns(g, m_edges=3, k=3, use_pallas=True)
    assert a.patterns == b.patterns
    assert (a.candidates, a.groups_expanded, a.groups_pruned) == \
        (b.candidates, b.groups_expanded, b.groups_pruned)


@pytest.mark.parametrize("e,n,d", [(64, 16, 8), (300, 50, 16), (1024, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_matmul(e, n, d, dtype):
    k = jax.random.PRNGKey(e + n)
    msg = jax.random.normal(k, (e, d), dtype)
    dst = jax.random.randint(jax.random.PRNGKey(1), (e,), 0, n)
    out = ops.segment_matmul(msg, dst, num_nodes=n, block_n=32, block_e=128)
    want = ref.segment_matmul_ref(msg, dst, n)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("f,v,d,b", [(5, 37, 8, 9), (40, 1000, 32, 16),
                                     (1, 8, 128, 3)])
def test_embedding_bag(f, v, d, b):
    k = jax.random.PRNGKey(f * v)
    table = jax.random.normal(k, (f, v, d))
    ids = jax.random.randint(jax.random.PRNGKey(2), (b, f), 0, v)
    out = ops.embedding_bag(table, ids)
    want = ref.embedding_bag_ref(table, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("h,s,d", [(2, 128, 32), (4, 256, 64), (1, 512, 16)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(h, s, d, causal, dtype):
    k = jax.random.PRNGKey(h * s)
    q = jax.random.normal(k, (h, s, d), dtype)
    kk = jax.random.normal(jax.random.PRNGKey(1), (h, s, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (h, s, d), dtype)
    out = ops.flash_attention(q, kk, v, causal=causal, block_q=64,
                              block_k=64)
    want = ref.flash_attention_ref(q, kk, v, causal=causal)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol)
