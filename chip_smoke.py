#!/usr/bin/env python3
"""Chip smoke test: the hybrid r-NN index on a TPU.

    python chip_smoke.py               # one chip: the index phase
    python chip_smoke.py --four-chips  # four chips: the sharded index only

Drives the main path through the entry points a user calls, at a
deployment size, and checks every answer against an independent
reference: a plain float32 brute-force range search in jnp on the
device (direct differences; no index, no repo kernel).

* Index phase: a ``DynamicHybridIndex`` over a corpus with the shape of
  ann-benchmarks' ``sift-128-euclidean`` (1,000,000 x 128 float32, L2),
  generated from ``--seed`` with a dense core, so some queries report a
  large share of the corpus and others a handful of rows.  Build, insert
  2.5 delta capacities (a level-0 freeze), delete 1% of the ids, then
  query 256 queries (half dense, half sparse) forced linear, forced LSH
  and hybrid-routed.  Linear answers must equal the reference exactly,
  except for points within the float32 band ``|d^2 - r^2| <= 1e-5 *
  (|q|^2 + |x|^2)``; LSH and hybrid answers must be subsets of it; no
  deleted id may be reported; hybrid routing must send at least a
  quarter of the queries each way.
* The served path (``RetrievalService.submit`` -> ``drain_batches``
  with yi-6b as the encoder) is the benchmark cell
  ``yi6b-served.steady`` (``chipbench/``), checked by its reference.
* ``--four-chips``: only the mesh-sharded index
  (``ShardedDynamicHybridIndex`` on ``jax.make_mesh((4,), ("data",))``),
  churned the same way; its forced-route answers must equal those of a
  single-host ``DynamicHybridIndex`` built on the surviving rows, and no
  (shard, query) output may reach ``max_out`` (that cut is silent).

It runs in one process and never falls back: without a TPU, or when a
check fails, it exits non-zero and prints no result line.  Wall times
printed along the way are set-up and compile times of a cold process,
not benchmark numbers.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.lsh import make_family  # noqa: E402
from repro.core.lsh.tables import bucket_counts  # noqa: E402
from repro.data import clustered_dataset  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.streaming import (DynamicHybridIndex,  # noqa: E402
                             ShardedDynamicHybridIndex)

# Index phase: ann-benchmarks sift-128-euclidean shape (rows, dims, f32,
# L2).  Half the rows sit in a tight dense core (the paper's regime:
# those queries report half the corpus and route to the linear scan).
N, D = 1_000_000, 128
DENSE_FRAC, CORE_SCALE, N_CLUSTERS = 0.5, 0.002, 32
L, CAP, HLL_M = 20, 128, 64
DELTA_CAP = 16_384
N_INSERT = 2 * DELTA_CAP + DELTA_CAP // 2     # forces a level-0 freeze
DELETE_FRAC = 0.01
NQ = 256        # one routed batch: the linear route returns (Q, N) buffers
MAX_OUT = 16_384                              # four-chip: per (shard, query)

def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    """Exit with status 1 (and no result line) unless ``ok``."""
    if not ok:
        raise SystemExit(f"FAIL: {msg}")


@contextlib.contextmanager
def timed(name: str):
    t0 = time.perf_counter()
    yield
    log(f"[{name}] {time.perf_counter() - t0:.1f} s wall "
        f"(set-up and compile, not a benchmark)")


def require_tpu(count: int) -> dict:
    """The device line; exits non-zero unless ``count`` TPUs are here
    and the kernels dispatch to compiled Pallas."""
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform!r} devices; "
                         "this smoke test runs only on the chip")
    if len(devs) != count:
        raise SystemExit(f"need exactly {count} TPU chips, JAX found "
                         f"{len(devs)}")
    impl = ops.resolve_impl()
    if impl != "pallas":
        raise SystemExit(f"kernels dispatch to {impl!r}, not 'pallas'")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} kernels={impl}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# ----------------------------------------------------------- reference
@jax.jit
def ref_sq_dists(q: jax.Array, x: jax.Array) -> jax.Array:
    """(Q, d) x (N, d) -> (Q, N) squared L2, one query at a time by
    direct differences (no expansion, so no cancellation)."""
    return jax.lax.map(lambda qi: jnp.sum(jnp.square(x - qi), axis=-1), q)


@jax.jit
def ref_range(q, x, alive, r2):
    """The reference answer: ``inside`` (Q, N) = live and d^2 <= r^2;
    ``band`` (Q, N) = within float32 reach of the radius, where either
    answer is accepted from a kernel that expands |q|^2 + |x|^2 - 2 q.x."""
    d2 = ref_sq_dists(q, x)
    tol = 1e-5 * (jnp.sum(q * q, -1)[:, None] + jnp.sum(x * x, -1)[None, :])
    return (d2 <= r2) & alive[None, :], jnp.abs(d2 - r2) <= tol


NO_ID = np.int32(2**31 - 1)      # sorts after every id
ROW_CHUNK = 64                     # result rows compared per dispatch


@jax.jit
def reported_sorted(ids, mask):
    """(G, C) result buffers -> each row's reported ids, sorted, with
    unreported slots set to ``NO_ID`` (sort + gather only: a scatter
    over (Q, N) would be the slowest op on the chip)."""
    return jnp.sort(jnp.where(mask, ids.astype(jnp.int32), NO_ID), axis=-1)


@jax.jit
def _tally(s, ref, band, alive):
    """Counts of one row chunk of sorted reported ids ``s`` against the
    reference rows ``ref``/``band`` (see ``compare``)."""
    n = ref.shape[1]
    valid = s < n
    at = jnp.minimum(s, n - 1)
    in_ref = jnp.take_along_axis(ref, at, 1) & valid
    in_band = jnp.take_along_axis(band, at, 1) & valid
    i32 = functools.partial(jnp.sum, dtype=jnp.int32)
    return jnp.stack([
        i32(valid),                                           # reported
        i32(ref),                                             # reference
        i32(valid & ~in_ref & ~in_band),                      # outside r
        i32(valid & ~alive[at]),                              # deleted
        i32(valid[:, 1:] & (s[:, 1:] == s[:, :-1])),          # duplicates
        i32(ref & ~band) - i32(in_ref & ~in_band),            # missing
        i32(band),                                            # band
        i32(band & ref) - i32(in_ref & in_band)
        + i32(in_band & ~in_ref),                             # band diffs
    ])


@jax.jit
def _not_in(a, b, band):
    """Per row: ids of sorted ``a`` absent from sorted ``b``, outside
    and inside the float32 band."""
    n = band.shape[1]
    pos = jax.vmap(jnp.searchsorted)(b, a)
    found = jnp.take_along_axis(b, jnp.minimum(pos, b.shape[1] - 1), 1) == a
    miss = (a < n) & ~found
    in_band = jnp.take_along_axis(band, jnp.minimum(a, n - 1), 1)
    return (jnp.sum(miss & ~in_band, 1, dtype=jnp.int32),
            jnp.sum(miss & in_band, 1, dtype=jnp.int32))


def compare(name, rows, ids, mask, inside, band, alive, *, exact: bool):
    """Check one result group (rows ``rows`` of the query batch) against
    the reference; returns its sorted reported ids, (G, C) on device."""
    s = reported_sorted(jnp.asarray(ids), jnp.asarray(mask))
    rows = np.asarray(rows)
    t = sum(np.asarray(_tally(s[i:i + ROW_CHUNK],
                              inside[rows[i:i + ROW_CHUNK]],
                              band[rows[i:i + ROW_CHUNK]], alive))
            for i in range(0, len(rows), ROW_CHUNK))
    rep, ref, extra, dead, dup, missing, n_band, band_diff = (
        int(v) for v in t)
    log(f"  {name}: reported {rep}, reference {ref}, outside r {extra}, "
        f"deleted {dead}, duplicates {dup}, missing {missing}; float32 "
        f"band: {n_band} points, {band_diff} answered differently")
    check(dup == 0, f"{name}: an id was reported twice")
    check(dead == 0, f"{name}: a deleted id was reported")
    check(extra == 0, f"{name}: {extra} reported ids lie outside r")
    if exact:
        check(missing == 0, f"{name}: {missing} ids within r missing")
    return s


def query_groups(res):
    """(rows, (ids, dists, mask)) per route of a ``QueryResult``."""
    return [(name, idx, out) for name, idx, out in
            (("lsh", res.lsh_idx, res.lsh_out),
             ("linear", res.lin_idx, res.lin_out)) if out is not None]


# --------------------------------------------------------- index data
def make_corpus(seed: int, n: int, d: int):
    """Corpus rows plus NQ queries: half fresh draws from the dense core,
    half sparse corpus rows nudged off their source."""
    x = clustered_dataset(n, d, n_clusters=N_CLUSTERS,
                          dense_core_frac=DENSE_FRAC, core_scale=CORE_SCALE,
                          seed=seed)
    # same seed, same cluster centres: every draw is a dense-core point
    dense = clustered_dataset(NQ // 2, d, n_clusters=N_CLUSTERS,
                              dense_core_frac=1.0, core_scale=CORE_SCALE,
                              seed=seed)
    centre = dense.mean(axis=0)
    spread = np.linalg.norm(dense - centre, axis=1).mean()
    rng = np.random.default_rng(seed + 1)
    cand = rng.choice(n, size=8 * NQ, replace=False)
    far = cand[np.linalg.norm(x[cand] - centre, axis=1) > 10 * spread]
    check(len(far) >= NQ // 2, "too few sparse rows to draw queries from")
    sparse = x[far[:NQ // 2]] + CORE_SCALE * rng.normal(
        size=(NQ // 2, d)).astype(np.float32)
    q = np.concatenate([dense, sparse]).astype(np.float32)
    return x, q


def pick_radius(q_dev, x_dev) -> float:
    """A third of the sparse queries' median distance to their
    second-nearest row (the nearest is the row each was nudged off):
    sparse queries report a handful of rows, dense ones the whole core."""
    d2 = ref_sq_dists(q_dev[NQ // 2:], x_dev)
    second = -jax.lax.top_k(-d2, 2)[0][:, 1]
    return float(np.sqrt(np.median(np.asarray(second)))) / 3


def churn(index, x: np.ndarray, seed: int) -> np.ndarray:
    """build -> insert (a level-0 freeze) -> delete 1%; returns the
    (N,) live mask over external ids (external id == row of ``x``)."""
    n0 = x.shape[0] - N_INSERT
    index.build(x[:n0])
    index.insert(jnp.asarray(x[n0:]))
    rng = np.random.default_rng(seed + 2)
    gone = rng.choice(x.shape[0], size=int(DELETE_FRAC * x.shape[0]),
                      replace=False)
    removed = index.delete(gone.tolist())
    check(removed == len(gone), f"deleted {removed} of {len(gone)} ids")
    alive = np.ones(x.shape[0], bool)
    alive[gone] = False
    return alive


# -------------------------------------------------------------- phases
def _num_buckets(n: int) -> int:
    """Power-of-two bucket space, ~8 rows per bucket."""
    return 1 << max(int(np.ceil(np.log2(n / 8))), 4)


def index_phase(seed: int, n: int = N, d: int = D) -> None:
    log(f"== index phase: DynamicHybridIndex, {n} x {d} float32, L2")
    with timed("corpus"):
        x, q = make_corpus(seed, n, d)
        x_dev, q_dev = jax.device_put(x), jax.device_put(q)
        r = pick_radius(q_dev, x_dev)
    log(f"radius {r:.6g} (from the reference distance distribution)")
    fam = make_family("l2", d=d, L=L, r=r)
    index = DynamicHybridIndex(
        fam, num_buckets=_num_buckets(n), m=HLL_M, cap=CAP,
        delta_capacity=DELTA_CAP, key=seed)
    with timed("build + churn"):
        alive = churn(index, x, seed)
    st = index.index_stats()
    log(f"index: {index.n} live rows, {st['segments']} frozen segments, "
        f"delta {st['delta_count']}/{DELTA_CAP}, freezes {st['freezes']}")
    check(st["freezes"] >= 1, "the inserts froze no level-0 segment")
    alive_dev = jax.device_put(alive)
    inside, band = ref_range(q_dev, x_dev, alive_dev, jnp.float32(r * r))
    del x_dev                    # the index holds its own copy
    sizes = np.asarray(jnp.sum(inside, axis=1))
    log(f"reference output sizes: dense median "
        f"{int(np.median(sizes[:NQ // 2]))}, sparse median "
        f"{int(np.median(sizes[NQ // 2:]))}, max {int(sizes.max())}, "
        f"min {int(sizes.min())}")
    for force in ("linear", "lsh", None):
        name = force or "hybrid"
        with timed(f"query {name}"):
            res = index.query(q_dev, r, force=force)
            for route, rows, (ids, _, mask) in query_groups(res):
                compare(f"{name}/{route}", rows, ids, mask, inside, band,
                        alive_dev, exact=route == "linear")
        if force is None:
            log(f"  hybrid frac_linear {res.frac_linear:.4f} "
                f"({res.n_linear} of {NQ} queries)")
            check(0.25 <= res.frac_linear <= 0.75,
                  "hybrid routing sent fewer than a quarter of the "
                  "queries one way")
        del res


def four_chip_phase(seed: int, n: int = N, d: int = D) -> None:
    mesh = jax.make_mesh((4,), ("data",))
    log(f"== four-chip phase: ShardedDynamicHybridIndex on mesh "
        f"{dict(mesh.shape)} vs a single-host DynamicHybridIndex, "
        f"{n} x {d} float32, L2")
    with timed("corpus"):
        x, q = make_corpus(seed, n, d)
        x_dev, q_dev = jax.device_put(x), jax.device_put(q)
        # per query at most 2 * MAX_OUT rows within r: ~MAX_OUT / 2 per
        # shard, so no (shard, query) output comes near the cut
        d2 = ref_sq_dists(q_dev, x_dev)
        kth = -jax.lax.top_k(-d2, 2 * MAX_OUT)[0][:, -1]
        r = float(np.sqrt(np.asarray(kth).min()))
        del d2
    log(f"radius {r:.6g} (every query has <= {2 * MAX_OUT} rows within "
        f"it)")
    fam = make_family("l2", d=d, L=L, r=r)
    common = dict(num_buckets=_num_buckets(n), m=HLL_M, cap=CAP, key=seed)
    # a delta per shard: the same total capacity, so the same op stream
    # freezes level-0 entries on every shard
    sharded = ShardedDynamicHybridIndex(
        fam, mesh=mesh, max_out=MAX_OUT,
        delta_capacity=DELTA_CAP // mesh.shape["data"], **common)
    with timed("sharded build + churn"):
        alive = churn(sharded, x, seed)
    st = sharded.index_stats()
    log(f"sharded index: {sharded.n} live rows over {sharded.shards} "
        f"shards, freezes {st['freezes']}")
    check(st["freezes"] >= 1, "the inserts froze no level-0 segment")
    single = DynamicHybridIndex(fam, delta_capacity=DELTA_CAP, **common)
    keep = np.nonzero(alive)[0]
    with timed("single-host build on the surviving rows"):
        single.build(x[keep], ids=keep)
    alive_dev = jax.device_put(alive)
    inside, band = ref_range(q_dev, x_dev, alive_dev, jnp.float32(r * r))
    del x_dev
    # LSH answers can match only where no probed bucket exceeds cap
    # (gathers keep the first cap ids of a bucket, per shard and level)
    qb = fam.bucket_ids(single.params, q_dev, common["num_buckets"])
    biggest = np.asarray(jnp.max(bucket_counts(
        single.stack.segments[0].seg.tables, qb), axis=1))
    whole = biggest <= CAP
    log(f"reference output sizes: max {int(jnp.max(jnp.sum(inside, 1)))}; "
        f"{int(whole.sum())} of {NQ} queries probe no bucket over cap")
    # half the batch per query: device 0 holds the single-host index and
    # the reference beside its shard
    half = NQ // 2
    for force in ("linear", "lsh"):
        with timed(f"query {force}"):
            for lo in (0, half):
                _four_chip_query(sharded, single, force, q_dev, r, lo,
                                 lo + half, inside, band, alive_dev, whole)


def _four_chip_query(sharded, single, force, q_dev, r, lo, hi, inside,
                     band, alive, whole) -> None:
    """Queries ``lo:hi`` on both indexes: each against the reference,
    then sharded against single-host."""
    rows = np.arange(lo, hi)
    name = f"{force} queries {lo}:{hi}"
    sh = sharded.query(q_dev[lo:hi], r, force=force)
    per = sh.mask.sum(axis=-1)                               # (S, Q)
    log(f"  sharded/{name}: largest (shard, query) output "
        f"{int(per.max())} of max_out {sh.mask.shape[-1]}")
    check(int(per.max()) < sh.mask.shape[-1],
          f"sharded/{name}: a (shard, query) output reached max_out and "
          "was cut")
    flat = lambda a: a.transpose(1, 0, 2).reshape(len(rows), -1)
    got_sh = compare(f"sharded/{name}", rows, flat(sh.ids), flat(sh.mask),
                     inside, band, alive, exact=force == "linear")
    del sh
    one = single.query(q_dev[lo:hi], r, force=force)
    (_, idx, (ids, _, mask)), = query_groups(one)
    check(np.array_equal(idx, np.arange(len(rows))),
          "a forced route split the batch")
    got_one = compare(f"single/{name}", rows, ids, mask, inside, band,
                      alive, exact=force == "linear")
    del one, ids, mask
    a_out, a_band = _not_in(got_sh, got_one, band[lo:hi])
    b_out, b_band = _not_in(got_one, got_sh, band[lo:hi])
    same = np.asarray((a_out == 0) & (b_out == 0))
    want = whole[lo:hi] if force == "lsh" else np.ones(len(rows), bool)
    n_band = int(np.asarray(a_band + b_band)[want].sum())
    log(f"  {name}: sharded == single-host on {int(same[want].sum())} of "
        f"{int(want.sum())} queries checked ({n_band} float32-band points "
        f"differ)")
    check(bool(same[want].all()),
          f"{name}: sharded and single-host answers differ")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-index phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    device = require_tpu(4 if args.four_chips else 1)
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        index_phase(args.seed)
    for dev in jax.devices():
        peak = dev.memory_stats().get("peak_bytes_in_use")
        log(f"{dev}: peak_bytes_in_use {peak}")
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s wall "
        f"(set-up and compile, not a benchmark)")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
