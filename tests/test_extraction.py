"""Result extraction: one compacted transfer gives the per-row answers.

``QueryResult.reported(i)``/``neighbors(i)`` must return, element for
element and in order, what the per-row masked extraction returns: query
i's row of its group's padded ``(ids, dists, mask)`` buffers, filtered
by the mask on the host.  That extraction is recomputed here from
``lsh_out``/``lin_out``, the reference every case is held to.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CostModel
from repro.core import engine
from repro.core.engine import QueryResult, packed_length, partition_indices
from repro.core.lsh import make_family
from repro.kernels import ops
from repro.streaming import CompactionPolicy, DynamicHybridIndex

D, L = 8, 4


def per_row(res, i):
    """Query ``i``'s row of its group's buffers, filtered by its mask."""
    for idx, out in ((res.lsh_idx, res.lsh_out), (res.lin_idx, res.lin_out)):
        if out is None:
            continue
        pos = np.nonzero(np.asarray(idx) == i)[0]
        if len(pos):
            ids, dists, mask = (np.asarray(a[pos[0]]) for a in out)
            return ids[mask], dists[mask]
    raise KeyError(i)


def assert_matches(res, order):
    for i in order:
        want_ids, want_dists = per_row(res, i)
        for ids, dists in (res.reported(i), (res.neighbors(i), None)):
            assert isinstance(ids, np.ndarray)
            assert ids.dtype == want_ids.dtype
            np.testing.assert_array_equal(ids, want_ids)
            if dists is not None:
                assert dists.dtype == want_dists.dtype
                np.testing.assert_array_equal(dists.view(np.uint32),
                                              want_dists.view(np.uint32))


@pytest.fixture(scope="module")
def churned():
    """Frozen segments plus the delta, with deletes."""
    rng = np.random.default_rng(0)
    # a tight cluster (linear route) and spread rows (LSH route)
    x = np.concatenate([rng.normal(size=(256, D)) * 0.05,
                        rng.normal(size=(256, D)) * 3.0]).astype(np.float32)
    idx = DynamicHybridIndex(
        make_family("l2", d=D, L=L, r=1.0), num_buckets=256, m=32, cap=256,
        key=0, delta_capacity=128, cost_model=CostModel(alpha=1.0, beta=1.0),
        policy=CompactionPolicy(delta_fill=1.0, tombstone_ratio=2.0,
                                fanout=2))
    idx.build(x[:384])
    idx.insert(x[384:])                 # a freeze: frozen levels + delta
    idx.delete([3, 300, 301, 450])
    assert len(idx.stack.segments) >= 1
    far = np.full((2, D), 100.0, np.float32)     # rows with no answer
    q = np.concatenate([x[::40][:10], far])       # Q = 12: padded groups
    return idx, jnp.asarray(q)


ORDERS = {"forward": lambda q: list(range(q)),
          "backward": lambda q: list(range(q))[::-1],
          "five_first": lambda q: [5] + [i for i in range(q) if i != 5]}


@pytest.mark.parametrize("order", sorted(ORDERS))
@pytest.mark.parametrize("force", [None, "lsh", "linear"])
def test_index_result_matches_per_row(churned, force, order):
    idx, q = churned
    res = idx.query(q, 1.2, force=force)
    nq = q.shape[0]
    if force is None:      # both routes ran, each group padded
        assert res.lsh_out is not None and res.lin_out is not None
    else:                  # one route group is empty
        assert (res.lsh_out is None) == (force == "linear")
        assert len(res.lsh_idx if force == "lsh" else res.lin_idx) == 16
    assert_matches(res, ORDERS[order](nq))
    assert len(res.reported(nq - 1)[0]) == 0       # the far rows
    assert res.neighbor_sets() == {i: set(per_row(res, i)[0].tolist())
                                   for i in range(nq)}
    with pytest.raises(KeyError):
        res.reported(nq)


def _synthetic(rng, lsh_rows, lin_rows, widths, density, nq, impl=None):
    """A QueryResult over random buffers; each group's padding repeats
    its last query with a different mask, which must never be read."""
    use = np.zeros(nq, bool)
    use[rng.permutation(nq)[:lsh_rows]] = True
    lsh_idx, lin_idx = partition_indices(use)

    def group(idx, width, p):
        if not len(idx):
            return None
        shape = (len(idx), width)
        mask = rng.random(shape) < p
        ids = np.where(mask, rng.integers(0, 2**31 - 1, shape),
                       engine.EXT_SENTINEL).astype(np.int32)
        dists = rng.random(shape).astype(np.float32)
        return tuple(jnp.asarray(a) for a in (ids, dists, mask))

    assert len(set(lin_idx.tolist())) == nq - lsh_rows == lin_rows
    return QueryResult(route=None, lsh_idx=lsh_idx, lin_idx=lin_idx,
                       lsh_out=group(lsh_idx, widths[0], density[0]),
                       lin_out=group(lin_idx, widths[1], density[1]),
                       n_queries=nq, impl=impl)


@pytest.mark.parametrize("impl", [None, "pallas_interpret"])
@pytest.mark.parametrize("case", [
    # (lsh rows, linear rows, widths, densities)
    (5, 7, (24, 96), (0.2, 0.5)),     # both groups padded to 8
    (12, 0, (40, 0), (0.3, 0.0)),     # empty linear group
    (0, 3, (0, 64), (0.0, 0.9)),
    (6, 6, (32, 32), (0.0, 0.0)),     # no answer at all
])
def test_synthetic_result_matches_per_row(case, impl, monkeypatch):
    monkeypatch.setattr(engine, "PAIRS_FLOOR", 16)
    lsh_rows, lin_rows, widths, density = case
    rng = np.random.default_rng(lsh_rows * 10 + lin_rows)
    res = _synthetic(rng, lsh_rows, lin_rows, widths, density,
                     lsh_rows + lin_rows, impl=impl)
    assert_matches(res, list(rng.permutation(res.n_queries)))


@pytest.mark.parametrize("extra", [-1, 0, 1])
def test_totals_across_a_bucket_boundary(extra, monkeypatch):
    """Totals just under, at and just over a power of two fill their
    bucket or open the next one; the answers never change."""
    monkeypatch.setattr(engine, "PAIRS_FLOOR", 16)
    nq, width = 4, 40
    total = 64 + extra
    rng = np.random.default_rng(extra + 1)
    mask = np.zeros(nq * width, bool)
    mask[rng.choice(nq * width, total, replace=False)] = True
    mask = mask.reshape(nq, width)
    ids = np.where(mask, rng.integers(0, 1000, mask.shape),
                   engine.EXT_SENTINEL).astype(np.int32)
    dists = rng.random(mask.shape).astype(np.float32)
    idx = np.arange(nq, dtype=np.int32)
    res = QueryResult(route=None, lsh_idx=idx, lin_idx=idx[:0],
                      lsh_out=tuple(jnp.asarray(a)
                                    for a in (ids, dists, mask)),
                      lin_out=None, n_queries=nq)
    assert_matches(res, [3, 0, 2, 1])
    (packed,), where = res._host
    assert [len(a) for a in packed] == [packed_length(total)] * 2
    assert packed_length(total) == (64 if extra <= 0 else 128)
    assert where[:, 2].max() == total


@pytest.mark.parametrize("shape,density", [
    ((3, 300), 0.5),        # a partial 128-slot row at each row's end
    ((16, 1000), 0.9),      # several window flushes (8-row tiles)
    ((5, 129), 0.02),       # mostly empty 128-slot rows
    ((4, 2048), 1.0),       # every slot reported
    ((2, 640), 0.0),        # none
])
def test_pack_kernel_matches_reference(shape, density):
    rng = np.random.default_rng(shape[1])
    mask = rng.random(shape) < density
    ids = rng.integers(-2**31, 2**31 - 1, shape, dtype=np.int32)
    dists = rng.standard_normal(shape).astype(np.float32)
    total = int(mask.sum())
    length = max(128, 1 << max(total - 1, 0).bit_length())
    args = (jnp.asarray(ids), jnp.asarray(dists), jnp.asarray(mask), length)
    want = ops.pack_reported(*args, impl="ref")
    got = ops.pack_reported(*args, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(want[0])[:total], ids[mask])
    for w, g in zip(want, got):
        assert g.dtype == w.dtype and g.shape == (length,)
        np.testing.assert_array_equal(np.asarray(g).view(np.uint32),
                                      np.asarray(w).view(np.uint32))


def test_packed_length_buckets():
    floor = engine.PAIRS_FLOOR
    assert packed_length(0) == packed_length(1) == floor
    assert packed_length(floor) == floor
    assert packed_length(floor + 1) == 2 * floor
    assert packed_length(8_080_000) == 1 << 23


def test_same_shapes_same_bucket_compile_nothing(churned):
    idx, q = churned
    compiles = []

    def listen(name, secs, **kw):
        if name.startswith("/jax/core/compile/"):
            compiles.append(name)

    def serve(rows):
        res = idx.query(rows, 1.2)
        return res, [res.reported(i) for i in range(rows.shape[0])]

    res1, _ = serve(q)
    q2 = jnp.asarray(np.asarray(q) + np.float32(1e-3))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        res2, _ = serve(q2)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert (len(res2.lsh_idx), len(res2.lin_idx)) == \
        (len(res1.lsh_idx), len(res1.lin_idx))
    assert [len(p[0]) for p in res2._host[0]] == \
        [len(p[0]) for p in res1._host[0]]
    assert compiles == [], compiles
