"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

np.random.seed(7)


# ------------------------------------------------------------- bitmap_query
@pytest.mark.parametrize("k,n", [(1, 64), (50, 1000), (128, 4096), (7, 333)])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
def test_bitmap_query(k, n, density):
    from repro.kernels.bitmap_query import bitmap_query
    from repro.kernels.bitmap_query.ref import bitmap_query_ref

    bm = jnp.asarray((np.random.rand(k, n) < density).astype(np.int8))
    mask = jnp.asarray(np.random.rand(k) < 0.3)
    assert bool(jnp.all(bitmap_query(bm, mask) == bitmap_query_ref(bm, mask)))


def test_bitmap_query_all_selected():
    from repro.kernels.bitmap_query import bitmap_query
    from repro.kernels.bitmap_query.ref import bitmap_query_ref

    bm = jnp.asarray((np.random.rand(20, 500) < 0.1).astype(np.int8))
    mask = jnp.ones(20, bool)
    assert bool(jnp.all(bitmap_query(bm, mask) == bitmap_query_ref(bm, mask)))


@pytest.mark.parametrize("q,k,n", [(1, 50, 1000), (3, 50, 1000), (8, 128, 4096), (2, 7, 333)])
def test_bitmap_query_batched(q, k, n):
    """Multi-mask entry (planner fusion): one launch ≡ q single-mask calls."""
    from repro.kernels.bitmap_query import bitmap_query, bitmap_query_batched
    from repro.kernels.bitmap_query.ref import bitmap_query_batched_ref

    bm = jnp.asarray((np.random.rand(k, n) < 0.1).astype(np.int8))
    masks = jnp.asarray(np.random.rand(q, k) < 0.3)
    out = bitmap_query_batched(bm, masks)
    assert out.shape == (q, n)
    assert bool(jnp.all(out == bitmap_query_batched_ref(bm, masks)))
    for i in range(q):
        assert bool(jnp.all(out[i] == bitmap_query(bm, masks[i])))


@pytest.mark.parametrize("w", [5, 100, 513, 1500])  # below / not a multiple of the 512-word tile
@pytest.mark.parametrize("k", [1, 7, 50])
@pytest.mark.parametrize("q", [1, 3, 8, 64])
def test_bitmap_query_packed_parity(q, k, w):
    """Packed word kernels (batched and single) ≡ the XLA word reduction
    ``dip_arr.query_any_batched_words``, bit for bit."""
    from repro.core import dip_arr
    from repro.kernels.bitmap_query.ops import bitmap_query_batched_packed, bitmap_query_packed

    rng = np.random.default_rng(q * 10_000 + k * 100 + w)
    plane = jnp.asarray(rng.integers(0, 2**32, (k, w), dtype=np.uint32))
    masks = jnp.asarray(rng.random((q, k)) < 0.3)
    store = dip_arr.DIPArr(bitmap=plane, k=k, n=32 * w, packed=True)
    ref = np.asarray(dip_arr.query_any_batched_words(store, masks))
    got = np.asarray(bitmap_query_batched_packed(plane, masks))
    assert got.dtype == np.uint32 and np.array_equal(got, ref)
    one = np.asarray(bitmap_query_packed(plane, masks[q - 1]))
    assert np.array_equal(one, ref[q - 1])


# -------------------------------------------------------------------- seg_mm
@pytest.mark.parametrize("n,e,d", [(64, 256, 16), (500, 2000, 64), (37, 91, 8),
                                   (1000, 5000, 128)])
@pytest.mark.parametrize("weighted", [False, True])
def test_seg_mm(n, e, d, weighted):
    from repro.kernels.seg_mm import seg_mm
    from repro.kernels.seg_mm.ref import seg_mm_ref

    x = jnp.asarray(np.random.randn(n, d).astype(np.float32))
    src = jnp.asarray(np.random.randint(0, n, e).astype(np.int32))
    dst = jnp.asarray(np.sort(np.random.randint(0, n, e)).astype(np.int32))
    w = jnp.asarray(np.random.rand(e).astype(np.float32)) if weighted else None
    got = seg_mm(x, src, dst, n, edge_weight=w, nt=64, ec=64)
    exp = seg_mm_ref(x, src, dst, n, edge_weight=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-5, atol=1e-5)


def test_seg_mm_unsorted_dst():
    """ops.seg_mm sorts internally (reverse-DI layout build)."""
    from repro.kernels.seg_mm import seg_mm
    from repro.kernels.seg_mm.ref import seg_mm_ref

    n, e, d = 50, 200, 32
    x = jnp.asarray(np.random.randn(n, d).astype(np.float32))
    src = jnp.asarray(np.random.randint(0, n, e).astype(np.int32))
    dst = jnp.asarray(np.random.randint(0, n, e).astype(np.int32))  # unsorted
    got = seg_mm(x, src, dst, n, nt=32, ec=32)
    exp = seg_mm_ref(x, src, dst, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------- flash_attention
@pytest.mark.parametrize(
    "b,sq,skv,hq,hkv,d,causal,window,cap",
    [
        (2, 128, 128, 4, 2, 32, True, None, None),
        (1, 256, 256, 8, 8, 64, True, 64, None),
        (1, 128, 128, 4, 1, 32, False, None, 50.0),
        (2, 128, 128, 8, 4, 64, True, 32, 30.0),
    ],
)
def test_flash_attention(b, sq, skv, hq, hkv, d, causal, window, cap):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref

    q = jnp.asarray(np.random.randn(b, sq, hq, d).astype(np.float32)) * 0.3
    k = jnp.asarray(np.random.randn(b, skv, hkv, d).astype(np.float32)) * 0.3
    v = jnp.asarray(np.random.randn(b, skv, hkv, d).astype(np.float32))
    got = flash_attention(q, k, v, causal=causal, window=window, cap=cap, bq=64, bkv=64)
    exp = flash_attention_ref(q, k, v, causal=causal, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref

    q = jnp.asarray(np.random.randn(1, 128, 4, 32), jnp.bfloat16) * 0.3
    k = jnp.asarray(np.random.randn(1, 128, 2, 32), jnp.bfloat16) * 0.3
    v = jnp.asarray(np.random.randn(1, 128, 2, 32), jnp.bfloat16)
    got = flash_attention(q, k, v)
    exp = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(exp, np.float32), rtol=2e-2, atol=2e-2)


# ------------------------------------------------------------- embedding_bag
@pytest.mark.parametrize("b,f,mh,v,d", [(8, 4, 3, 100, 16), (16, 26, 1, 500, 64),
                                        (32, 2, 8, 50, 32)])
def test_embedding_bag(b, f, mh, v, d):
    from repro.kernels.embedding_bag import embedding_bag_fields
    from repro.kernels.embedding_bag.ref import embedding_bag_ref

    t = jnp.asarray(np.random.randn(f, v, d).astype(np.float32))
    ix = jnp.asarray(np.random.randint(0, v, (b, f, mh)).astype(np.int32))
    got = embedding_bag_fields(t, ix, bt=8)
    exp = embedding_bag_ref(t, ix)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-5, atol=1e-6)


# --------------------------------------------- kernel-backed high-level paths
def test_dip_arr_kernel_path():
    from repro.core import build_dip_arr
    from repro.core.dip_arr import query_any

    bm = build_dip_arr(np.random.randint(0, 100, 50), np.random.randint(0, 8, 50),
                       k=8, n=100)
    mask = jnp.asarray(np.random.rand(8) < 0.5)
    a = query_any(bm, mask, impl="kernel")
    b = query_any(bm, mask, impl="scan")
    assert bool(jnp.all(a == b))


def test_spmm_kernel_path():
    from repro.graph.segment_ops import spmm_di

    n, e, d = 100, 400, 32
    x = jnp.asarray(np.random.randn(n, d).astype(np.float32))
    src = jnp.asarray(np.sort(np.random.randint(0, n, e)).astype(np.int32))
    dst = jnp.asarray(np.random.randint(0, n, e).astype(np.int32))
    a = spmm_di(x, src, dst, n, impl="kernel")
    b = spmm_di(x, src, dst, n, impl="segment")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
