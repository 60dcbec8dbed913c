"""Per-kernel allclose sweeps: Pallas (interpret=True) vs ref oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _pts(n, d, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=(n, d)).astype(dtype))


@pytest.mark.parametrize("metric", ["l2", "l1", "cosine"])
@pytest.mark.parametrize("shape", [(8, 16, 7), (100, 130, 70),
                                   (128, 256, 128), (33, 257, 129)])
def test_distance_kernels_match_ref(metric, shape):
    q, n, d = shape
    qa, xa = _pts(q, d), _pts(n, d)
    a = ops.pairwise_dist(qa, xa, metric, impl="pallas_interpret")
    b = ops.pairwise_dist(qa, xa, metric, impl="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_distance_kernel_dtypes(dtype):
    qa, xa = _pts(16, 32, dtype), _pts(64, 32, dtype)
    a = ops.pairwise_dist(qa, xa, "l2", impl="pallas_interpret")
    b = ops.pairwise_dist(qa, xa, "l2", impl="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("shape", [(4, 10, 1), (60, 200, 2), (128, 128, 8)])
def test_hamming_kernel_exact(shape):
    q, n, w = shape
    qc = jnp.asarray(RNG.integers(0, 2**32, (q, w), dtype=np.uint32))
    xc = jnp.asarray(RNG.integers(0, 2**32, (n, w), dtype=np.uint32))
    a = ops.hamming_dist(qc, xc, impl="pallas_interpret")
    b = ops.hamming_dist(qc, xc, impl="ref")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # cross-check against numpy bit counting
    qa, xa = np.asarray(qc), np.asarray(xc)
    expect = np.zeros((q, n), np.int64)
    for i in range(q):
        x = qa[i][None] ^ xa
        expect[i] = np.unpackbits(x.view(np.uint8), axis=1).sum(1)
    np.testing.assert_array_equal(np.asarray(a), expect)


@pytest.mark.parametrize("L,k", [(3, 8), (5, 31), (2, 32), (4, 40), (1, 64)])
def test_simhash_kernel_exact(L, k):
    x = _pts(130, 48)
    r = _pts(48, L * k)
    a = ops.simhash_fingerprint(x, r, L=L, k=k, impl="pallas_interpret")
    b = ops.simhash_fingerprint(x, r, L=L, k=k, impl="ref")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (130, L, (k + 31) // 32)


def test_simhash_matches_family_packing():
    """Kernel fingerprints == families.SimHash.codes bit-for-bit."""
    from repro.core.lsh import SimHash
    fam = SimHash(d=32, L=4, k=17)
    params = fam.init(jax.random.PRNGKey(1))
    x = _pts(64, 32)
    a = fam.codes(params, x)
    b = ops.simhash_fingerprint(x, params["R"], L=4, k=17, impl="ref")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("q,L,m", [(8, 3, 32), (64, 20, 128), (5, 1, 64)])
def test_hll_merge_kernel(q, L, m):
    regs = jnp.asarray(RNG.integers(0, 25, (q, L, m)).astype(np.uint8))
    a = ops.hll_merge_estimate(regs, impl="pallas_interpret")
    b = ops.hll_merge_estimate(regs, impl="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_l2_transform_threshold():
    """ops returns squared L2; radius transform must square r."""
    assert ops.metric_radius_transform("l2", 3.0) == 9.0
    assert ops.metric_radius_transform("cosine", 0.5) == 0.5


# ---------------------------------------------------------------------------
# Fused query-path kernels (fused_scan.py) vs the composed oracles.
#
# Radii sit away from any realized distance, so the report masks are
# insensitive to float reassociation and must match EXACTLY (ids too);
# raw distances are allclose (the kernel and XLA reduce in different
# orders).  The "ref" impl *is* the composed pipeline, so dispatch-level
# bit-identity off-TPU holds by construction.
# ---------------------------------------------------------------------------
_FUSED_RADII = {"l2": 7.0, "l1": 55.0, "cosine": 0.9, "hamming": 300.0}


def _fused_pair(metric, q, n):
    if metric == "hamming":
        qa = jnp.asarray(RNG.integers(0, 2**32, (q, 3), dtype=np.uint32))
        xa = jnp.asarray(RNG.integers(0, 2**32, (n, 3), dtype=np.uint32))
    else:
        d = 37
        qa, xa = _pts(q, d), _pts(n, d)
    return qa, xa


@pytest.mark.parametrize("metric", ["l2", "l1", "cosine", "hamming"])
@pytest.mark.parametrize("q,n", [(8, 100), (33, 257)])
def test_fused_linear_scan_matches_ref(metric, q, n):
    qa, xa = _fused_pair(metric, q, n)
    r = _FUSED_RADII[metric]
    ia, da, ma = ops.fused_linear_scan(qa, xa, r, metric,
                                       impl="pallas_interpret")
    ib, db, mb = ops.fused_linear_scan(qa, xa, r, metric, impl="ref")
    assert ia.shape == da.shape == ma.shape == (q, n)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb))
    np.testing.assert_allclose(np.asarray(da), np.asarray(db),
                               rtol=3e-4, atol=3e-4)
    assert int(np.asarray(ma).sum()) > 0      # radii actually report


@pytest.mark.parametrize("metric", ["l2", "l1", "cosine", "hamming"])
def test_fused_lsh_scan_handcrafted_candidates(metric):
    """Duplicates, sentinel padding, and an all-sentinel (empty-bucket)
    row all mask identically in the kernel and the oracle."""
    n = 40
    qa, xa = _fused_pair(metric, 3, n)
    sent = n
    ids = jnp.asarray(np.array([
        [0, 0, 0, 1, 2, 2, 5, sent],            # duplicate runs
        [3, 7, 7, 9, sent, sent, sent, sent],   # sentinel tail
        [sent] * 8,                             # empty bucket row
    ], np.int32))
    ids = jnp.sort(ids, axis=-1)
    r = _FUSED_RADII[metric]
    ia, da, ma = ops.fused_lsh_scan(xa, ids, qa, r, metric,
                                    impl="pallas_interpret")
    ib, db, mb = ops.fused_lsh_scan(xa, ids, qa, r, metric, impl="ref")
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb))
    ma_np, da_np = np.asarray(ma), np.asarray(da)
    np.testing.assert_allclose(da_np[ma_np], np.asarray(db)[ma_np],
                               rtol=3e-4, atol=3e-4)
    assert not ma_np[2].any()                  # all-sentinel row reports 0
    # duplicates report at most once: masked ids are unique per query
    for qi in range(2):
        rep = np.asarray(ia)[qi][ma_np[qi]]
        assert len(rep) == len(set(rep.tolist()))


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_fused_lsh_scan_wide_rows(metric):
    """Rows wider than fit 128 slabs at once (d = 1000 pads to 1024:
    64 slabs in flight per group, two groups per 128-candidate tile),
    candidates at every offset inside their 8-row slab, n not a
    multiple of 8: identical to the oracle."""
    n, q, d = 203, 9, 1000
    qa, xa = _pts(q, d), _pts(n, d)
    ids = jnp.sort(jnp.asarray(RNG.integers(0, n + 1, (q, 256),
                                            dtype=np.int32)), axis=-1)
    r = 44.0 if metric == "l2" else 0.99
    ia, da, ma = ops.fused_lsh_scan(xa, ids, qa, r, metric,
                                    impl="pallas_interpret")
    ib, db, mb = ops.fused_lsh_scan(xa, ids, qa, r, metric, impl="ref")
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb))
    ma_np = np.asarray(ma)
    np.testing.assert_allclose(np.asarray(da)[ma_np], np.asarray(db)[ma_np],
                               rtol=3e-4, atol=3e-4)
    assert 0 < int(ma_np.sum()) < ma_np.size     # the radius splits


@pytest.mark.parametrize("metric", ["l2", "hamming"])
def test_fused_lsh_search_end_to_end(metric):
    """lsh_search with real tables + multi-probe tidx + cap truncation:
    interpret and ref dispatches agree on ids/mask exactly."""
    from repro.core.lsh.tables import build_tables
    from repro.core.search import lsh_search
    n, q, L, B, cap = 150, 33, 4, 8, 2        # tiny cap => truncation
    qa, xa = _fused_pair(metric, q, n)
    bids = jnp.asarray(RNG.integers(0, B, size=(n, L), dtype=np.int32))
    tables = build_tables(jnp.arange(n, dtype=jnp.int32), bids, B, 16)
    tidx = jnp.asarray(np.repeat(np.arange(L), 2).astype(np.int32))
    qb = jnp.asarray(RNG.integers(0, B, size=(q, L * 2), dtype=np.int32))
    r = _FUSED_RADII[metric]
    a = lsh_search(xa, tables, qb, qa, r, metric, cap, q_chunk=16,
                   tidx=tidx, impl="pallas_interpret")
    b = lsh_search(xa, tables, qb, qa, r, metric, cap, q_chunk=16,
                   tidx=tidx, impl="ref")
    assert a[0].shape == (q, L * 2 * cap)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(b[2]))
    np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("nq", [7, 32, 33, 65])
def test_search_chunking_pads_odd_batches(nq):
    """No batch size falls back to full materialization: results are
    invariant to q_chunk (chunked == unchunked == chunk-padded)."""
    from repro.core.search import linear_search
    qa, xa = _fused_pair("l2", nq, 97)
    base = linear_search(xa, qa, 7.0, "l2", impl="ref", q_chunk=0)
    for q_chunk in (16, 32):
        got = linear_search(xa, qa, 7.0, "l2", impl="ref", q_chunk=q_chunk)
        for ga, ba in zip(got, base):
            assert ga.shape == ba.shape == (nq, 97)
            np.testing.assert_array_equal(np.asarray(ga), np.asarray(ba))
