"""Per-kernel shape/dtype sweeps + hypothesis properties vs ref.py oracles."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels import segment_sum, embedding_bag, flash_decode
from repro.kernels import ref


# ------------------------------------------------------------------ segsum
@pytest.mark.parametrize("E,D,n", [(64, 8, 10), (512, 128, 100), (1000, 16, 7),
                                   (2048, 1, 2048), (3, 4, 5), (513, 32, 40)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_segsum_sweep(E, D, n, dtype):
    rng = np.random.default_rng(E * D + n)
    rows = np.sort(rng.integers(0, n, size=E)).astype(np.int32)
    if dtype == jnp.int32:
        vals = rng.integers(-5, 6, size=(E, D)).astype(np.int32)
    else:
        vals = rng.normal(size=(E, D)).astype(np.float32)
    if D == 1:
        vals = vals[:, 0]
    got = segment_sum(jnp.asarray(vals), jnp.asarray(rows), n, block_edges=128)
    want = ref.segment_sum_ref(jnp.asarray(vals), jnp.asarray(rows), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_segsum_bfloat16():
    rng = np.random.default_rng(0)
    rows = np.sort(rng.integers(0, 50, size=512)).astype(np.int32)
    vals = rng.normal(size=(512, 64)).astype(np.float32)
    got = segment_sum(jnp.asarray(vals, jnp.bfloat16), jnp.asarray(rows), 50,
                      block_edges=128)
    want = ref.segment_sum_ref(jnp.asarray(vals), jnp.asarray(rows), 50)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=2e-2, atol=2e-1
    )


@given(st.data())
@settings(max_examples=30, deadline=None)
def test_segsum_property(data):
    E = data.draw(st.integers(1, 300))
    n = data.draw(st.integers(1, 50))
    D = data.draw(st.sampled_from([1, 3, 8]))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    rows = np.sort(rng.integers(0, n, size=E)).astype(np.int32)
    vals = rng.normal(size=(E, D)).astype(np.float32)
    got = segment_sum(jnp.asarray(vals), jnp.asarray(rows), n, block_edges=64)
    want = ref.segment_sum_ref(jnp.asarray(vals), jnp.asarray(rows), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)
    # linearity: segsum(a+b) == segsum(a) + segsum(b)
    vals2 = rng.normal(size=(E, D)).astype(np.float32)
    lhs = segment_sum(jnp.asarray(vals + vals2), jnp.asarray(rows), n, block_edges=64)
    rhs = got + segment_sum(jnp.asarray(vals2), jnp.asarray(rows), n, block_edges=64)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), rtol=1e-5, atol=1e-4)


# ------------------------------------------------------------ embedding_bag
@pytest.mark.parametrize("N,D,B,L", [(100, 16, 4, 3), (1000, 64, 8, 10),
                                     (37, 128, 16, 5), (10, 8, 1, 1)])
@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_embedding_bag_sweep(N, D, B, L, mode):
    rng = np.random.default_rng(N + B)
    table = rng.normal(size=(N, D)).astype(np.float32)
    idx = rng.integers(-1, N, size=(B, L)).astype(np.int32)  # -1 = masked
    w = rng.uniform(0.5, 2.0, size=(B, L)).astype(np.float32)
    got = embedding_bag(jnp.asarray(table), jnp.asarray(idx), jnp.asarray(w), mode=mode)
    want = ref.embedding_bag_ref(jnp.asarray(table), jnp.asarray(idx), jnp.asarray(w), mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_embedding_bag_unweighted_default():
    rng = np.random.default_rng(3)
    table = rng.normal(size=(50, 32)).astype(np.float32)
    idx = rng.integers(0, 50, size=(6, 4)).astype(np.int32)
    got = embedding_bag(jnp.asarray(table), jnp.asarray(idx))
    want = np.asarray(table)[idx].sum(axis=1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- flash_decode
@pytest.mark.parametrize("Hkv,G,S,d,blk", [(2, 4, 1024, 64, 256), (8, 1, 512, 128, 128),
                                           (1, 8, 2048, 64, 512), (4, 7, 512, 32, 128)])
def test_flash_decode_sweep(Hkv, G, S, d, blk):
    rng = np.random.default_rng(S + d)
    q = rng.normal(size=(Hkv * G, d)).astype(np.float32)
    k = rng.normal(size=(Hkv, S, d)).astype(np.float32)
    v = rng.normal(size=(Hkv, S, d)).astype(np.float32)
    for cache_len in [S, S - 17, blk + 1, 1]:
        got = flash_decode(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           jnp.int32(cache_len), block_kv=blk)
        want = ref.flash_decode_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                    jnp.int32(cache_len))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_flash_decode_bf16():
    rng = np.random.default_rng(9)
    q = rng.normal(size=(8, 64)).astype(np.float32)
    k = rng.normal(size=(2, 512, 64)).astype(np.float32)
    v = rng.normal(size=(2, 512, 64)).astype(np.float32)
    got = flash_decode(jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
                       jnp.asarray(v, jnp.bfloat16), jnp.int32(511), block_kv=128)
    want = ref.flash_decode_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                jnp.int32(511))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=5e-2, atol=5e-2)


def test_segsum_is_the_localcore_count_primitive():
    """The kernel computes Eq. 1/2 neighbor counts exactly."""
    from repro.graph import paper_example_graph
    g = paper_example_graph()
    src, dst = g.directed_pairs()
    core = np.array([3, 3, 3, 3, 2, 2, 2, 2, 1], np.int32)
    contrib = (core[dst] >= core[src]).astype(np.int32)
    cnt = segment_sum(jnp.asarray(contrib), jnp.asarray(src.astype(np.int32)), g.n,
                      block_edges=64)
    for v in range(g.n):
        exact = int((core[g.neighbors(v)] >= core[v]).sum())
        assert int(cnt[v]) == exact


# ------------------------------------------------------- block-skipping segsum
def test_segsum_active_skips_inactive_blocks_exactly():
    """SemiCore* discipline at the kernel level: skipped blocks contribute 0,
    active blocks match the plain segment sum."""
    from repro.kernels import segment_sum_active
    rng = np.random.default_rng(7)
    E, D, n, BE = 512, 8, 40, 64
    rows = np.sort(rng.integers(0, n, size=E)).astype(np.int32)
    vals = rng.normal(size=(E, D)).astype(np.float32)
    node_active = (rng.random(n) < 0.4)
    got = segment_sum_active(jnp.asarray(vals), jnp.asarray(rows),
                             jnp.asarray(node_active), n, block_edges=BE)
    # reference: zero out whole blocks with no active rows
    blk = rows.reshape(-1, BE)
    blk_act = node_active[blk].any(axis=1)
    masked = vals * np.repeat(blk_act, BE)[:, None]
    want = ref.segment_sum_ref(jnp.asarray(masked), jnp.asarray(rows), n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    # all-active degenerates to the plain kernel
    got_all = segment_sum_active(jnp.asarray(vals), jnp.asarray(rows),
                                 jnp.ones(n, bool), n, block_edges=BE)
    want_all = ref.segment_sum_ref(jnp.asarray(vals), jnp.asarray(rows), n)
    np.testing.assert_allclose(np.asarray(got_all), np.asarray(want_all),
                               rtol=1e-5, atol=1e-4)


def test_segsum_active_localcore_frontier_semantics():
    """Counts over only-frontier-touching blocks reproduce exact cnt values
    for frontier nodes (the SemiCore* per-superstep contract)."""
    from repro.kernels import segment_sum_active
    from repro.graph import chung_lu
    g = chung_lu(300, 1500, seed=3)
    src, dst = g.directed_pairs()
    core = g.degrees().astype(np.int32)
    frontier = np.zeros(g.n, bool)
    frontier[:50] = True  # contiguous CSR rows -> block skipping is real
    contrib = (core[dst] >= core[src]).astype(np.int32)
    got = segment_sum_active(jnp.asarray(contrib), jnp.asarray(src.astype(np.int32)),
                             jnp.asarray(frontier), g.n, block_edges=128)
    for v in range(50):
        exact = int((core[g.neighbors(v)] >= core[v]).sum())
        blk_lo = int(g.indptr[v]) // 128
        blk_hi = int(g.indptr[v + 1] - 1) // 128
        blocks_active = all(
            frontier[src[b * 128:(b + 1) * 128]].any()
            for b in range(blk_lo, blk_hi + 1))
        if blocks_active:
            assert int(got[v]) == exact


# ------------------------------------------------- interpret-mode policy
def test_interpret_mode_follows_the_platform(monkeypatch):
    """CPU interprets; a request for the other mode raises instead of
    quietly emulating (or quietly compiling) on the wrong platform."""
    from repro.kernels import resolve_interpret

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert resolve_interpret(None) is True
    assert resolve_interpret(True) is True
    with pytest.raises(ValueError, match="interpret=False"):
        resolve_interpret(False)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    with pytest.raises(ValueError, match="interpret=False"):
        resolve_interpret(None)


def test_interpret_mode_is_refused_on_a_tpu(monkeypatch):
    from repro.core.engine import resolve_backend
    from repro.kernels import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert resolve_interpret(None) is False
    with pytest.raises(ValueError, match="'tpu'"):
        resolve_interpret(True)
    with pytest.raises(ValueError, match="'tpu'"):
        resolve_backend("pallas-interpret")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(ValueError, match="'tpu'"):
        resolve_interpret(None)
