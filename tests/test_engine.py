"""Segment engine: the shared estimate/route/partition/search pipeline.

Checks that the compat wrappers (estimate_routes*) and the index-facing
engine path agree, that static segments are the dead-count zero case of
the unified estimator, that the deprecated ``core.router`` shim warns
and re-exports, and the satellite fixes (memory_stats before build,
exact n_linear).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CostModel, HybridLSHIndex
from repro.core.engine import (QueryEngine, SegmentEstimate, TableSegment,
                               estimate_routes, estimate_routes_dynamic,
                               finalize_route)
from repro.core.lsh import make_family
from repro.data import clustered_dataset
from repro.streaming import CompactionPolicy, DynamicHybridIndex
from repro.streaming import delta as delta_lib

D, L, B, M, CAP, R = 8, 4, 256, 32, 2048, 1.2


def _data(n=600):
    return np.asarray(clustered_dataset(n, D, n_clusters=8,
                                        dense_core_frac=0.2,
                                        core_scale=0.05, seed=0,
                                        metric="l2"), np.float32)


def _fam():
    return make_family("l2", d=D, L=L, r=1.0)


def test_static_estimate_matches_router_wrapper():
    """Index path (QueryEngine) == router compat wrapper, exactly."""
    x = _data()
    idx = HybridLSHIndex(_fam(), num_buckets=B, m=M, cap=CAP, key=0).build(x)
    q = jnp.asarray(x[::50][:8])
    qb = idx._bucket_fn(idx.params, q)
    a = idx.estimate(q)
    b = estimate_routes(idx.tables, qb, idx.cost_model, idx.n)
    for f in ("collisions", "cand_est", "lsh_cost", "use_lsh"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), f)
    assert a.linear_cost == b.linear_cost
    # static segment == unified path with zero dead counts
    seg = TableSegment(tables=idx.tables, n_live=idx.n, n_scan=idx.n)
    term = seg.estimate_terms(qb)
    assert term.dead_collisions is None
    c = finalize_route([term], idx.cost_model)
    np.testing.assert_array_equal(np.asarray(a.cand_est),
                                  np.asarray(c.cand_est))


def test_table_terms_one_program_for_any_row_count():
    """A table segment's estimate terms run as one jitted program whose
    compile does not depend on the segment's row count (segments of any
    size share it), and count each row's collisions exactly."""
    from repro.core import engine
    x = _data()
    q = jnp.asarray(x[::60][:8])
    sizes = []
    for n in (300, 600):
        idx = HybridLSHIndex(_fam(), num_buckets=B, m=M, cap=CAP,
                             key=0).build(x[:n])
        qb = idx._bucket_fn(idx.params, q)
        xb = np.asarray(idx._bucket_fn(idx.params, jnp.asarray(x[:n])))
        seg = TableSegment(tables=idx.tables, x=jnp.asarray(x[:n]), cap=CAP)
        term = seg.estimate_terms(qb)
        want = (np.asarray(qb)[:, None, :] == xb[None]).sum(axis=(1, 2))
        np.testing.assert_array_equal(np.asarray(term.collisions), want)
        sizes.append(engine._table_terms._cache_size())
    assert sizes[1] == sizes[0]


def test_dynamic_estimate_matches_router_wrapper():
    """Streaming index path == the tombstone-aware compat wrapper."""
    x = _data()
    dyn = DynamicHybridIndex(_fam(), num_buckets=B, m=M, cap=CAP, key=0,
                             delta_capacity=256,
                             policy=CompactionPolicy(2.0, 2.0))
    dyn.build(x[:450])
    dyn.insert(x[450:])
    dyn.delete(range(40, 120, 2))
    q = jnp.asarray(x[::40][:8])
    qb = dyn._bucket_fn(dyn.params, q)
    a = dyn.estimate(q)
    d_coll, d_dist = delta_lib.collision_stats(dyn.delta, qb)
    b = estimate_routes_dynamic(
        dyn.main.tables, qb, dyn.cost_model, dyn.n,
        tomb_counts=dyn.tomb.counts, delta_collisions=d_coll,
        delta_distinct=d_dist, n_scan=dyn.main.n + int(dyn.delta.count))
    for f in ("collisions", "cand_est", "lsh_cost", "use_lsh"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), f)
    assert a.linear_cost == b.linear_cost


def test_finalize_route_combines_sketch_and_exact_terms():
    cm = CostModel(alpha=1.0, beta=2.0)
    sketchless = SegmentEstimate(collisions=jnp.asarray([5, 0]),
                                 cand_exact=jnp.asarray([3, 0]),
                                 n_live=10, n_scan=10)
    r = finalize_route([sketchless], cm)
    np.testing.assert_allclose(np.asarray(r.cand_est), [3.0, 0.0])
    np.testing.assert_allclose(np.asarray(r.lsh_cost), [11.0, 0.0])
    assert r.linear_cost == 20.0
    assert np.asarray(r.use_lsh).tolist() == [True, True]
    # structural clamp: candSize can never exceed live collisions
    clamped = SegmentEstimate(collisions=jnp.asarray([2]),
                              cand_exact=jnp.asarray([7]),
                              n_live=10, n_scan=10)
    assert float(finalize_route([clamped], cm).cand_est[0]) == 2.0


@pytest.mark.parametrize("kind", ["static", "dynamic"])
def test_rows_over_the_cap_route_linear(kind):
    """A row whose probed buckets lose more than one bucket's worth of
    rows to the LSH route's cap (the share past it, summed; deleted rows
    hold slots too) goes linear whatever the cost model prices, so it
    gets its whole answer; a row that loses less, or a cap no bucket
    reaches, keeps its route."""
    x = _data()

    def index(cap):
        if kind == "static":
            return HybridLSHIndex(_fam(), num_buckets=B, m=M, cap=cap,
                                  key=0).build(x)
        dyn = DynamicHybridIndex(_fam(), num_buckets=B, m=M, cap=cap, key=0,
                                 delta_capacity=256)
        dyn.build(x)
        dyn.delete(range(0, 600, 15))
        return dyn

    tight, loose = index(8), index(CAP)
    q = jnp.asarray(x[1::25])
    qb = np.asarray(tight._bucket_fn(tight.params, q))
    xb = np.asarray(tight._bucket_fn(tight.params, jnp.asarray(x)))
    sizes = (qb[:, None, :] == xb[None, :, :]).sum(axis=1)     # (Q, L)
    over = np.where(sizes > 8, 1 - 8 / np.maximum(sizes, 1), 0).sum(1) > 1
    assert over.any() and (~over).any()
    assert ((sizes > 8).any(axis=1) & ~over).any()     # some, not enough
    a, b = tight.estimate(q), loose.estimate(q)
    np.testing.assert_array_equal(np.asarray(a.over_cap), over)
    assert not np.asarray(b.over_cap).any()
    np.testing.assert_array_equal(np.asarray(a.use_lsh),
                                  np.asarray(b.use_lsh) & ~over)
    hybrid, linear = tight.query(q, R), tight.query(q, R, force="linear")
    lsh = tight.query(q, R, force="lsh")
    rows = np.nonzero(over)[0]
    for i in rows:
        assert set(hybrid.neighbors(i).tolist()) == set(
            linear.neighbors(i).tolist())
    assert any(len(lsh.neighbors(i)) < len(linear.neighbors(i))
               for i in rows)


def test_memory_stats_before_build_is_zeroed():
    idx = HybridLSHIndex(_fam(), num_buckets=B, m=M, cap=CAP, key=0)
    st = idx.memory_stats()   # must not raise before build()
    assert st == {"perm_bytes": 0, "starts_bytes": 0, "hll_bytes": 0,
                  "hll_overhead_vs_data": 0.0}
    idx.build(_data(200))
    assert idx.memory_stats()["perm_bytes"] > 0


def test_query_result_n_linear_dedups_padding():
    x = _data(300)
    idx = HybridLSHIndex(_fam(), num_buckets=B, m=M, cap=CAP, key=0).build(x)
    q = jnp.asarray(x[:13])   # odd count: both groups get pow2 padding
    res = idx.query(q, R, force="linear")
    assert res.n_linear == 13 and len(res.lin_idx) == 16
    assert res.frac_linear == 1.0
    res = idx.query(q, R, force="lsh")
    assert res.n_linear == 0 and res.frac_linear == 0.0
    res = idx.query(q, R)
    assert res.n_linear == len(set(np.asarray(res.lin_idx).tolist()))
    engine = QueryEngine(idx.cost_model)
    assert engine.cost_model is idx.cost_model


def test_router_shim_warns_and_reexports():
    """The deprecated ``core.router`` shim: one intentional import site
    — it must warn and hand back the engine's objects unchanged, so it
    can be deleted (with this test) next release."""
    import importlib
    import warnings

    import repro.core.router as router_mod
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        router_mod = importlib.reload(router_mod)
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
    assert router_mod.estimate_routes is estimate_routes
    assert router_mod.estimate_routes_dynamic is estimate_routes_dynamic
    assert router_mod.finalize_route is finalize_route
