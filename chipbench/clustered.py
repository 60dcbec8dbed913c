"""Clustered corpora with a dense core, made on the device from a seed.

The regime the hybrid search is for (arXiv 1607.06179, abstract: data
"whose data distributions have diverse local density patterns", where
LSH can lose to a linear scan): a share of the rows in one tight
region, where every query reports near-n rows and the linear scan wins,
and sparse rows elsewhere, where LSH wins.  As
``repro.data.clustered_dataset``:
unit-norm cluster centres, points at ``cluster_scale`` around them, and
a share ``dense_core_frac`` of the rows in one tight core at
``core_scale`` around centre 0.  Two additions:

* each point of the clusters is the centre of a family of
  ``family_size`` near-duplicate rows at ``family_scale`` around it.  In
  128 dimensions the Gaussian clusters alone give no neighbourhood:
  every other row lies 3 to 4.5 away, and the core sits closer to every
  row than its own cluster's do, so no radius gives a sparse query tens
  of rows within it.  Families do, at a radius where LSH buckets stay
  small;
* a halo of ``halo_frac`` of the rows at ``halo_scale`` around the core
  centre.  At ``halo_scale`` = sqrt(2) ``family_scale`` a dense query
  sees the halo at the distance a sparse one sees its family, so r cuts
  through it: the linear route's answers hold rows at the edge of r,
  where a lower precision reports other sets.  Halo rows are never
  query sources.

The deployment (corpus, deletions, the radius's probe rows) is drawn
from the configuration's ``data_seed``; a run's ``--seed`` draws only
the query rows and their order, so seeds change the draws and not the
work.  (Drawn from a run's seed, the corpus and the index's hash draw
moved the router: on one v5e chip, 0 to 46% of the dense rows went to
the LSH route across four seeds, and the work per request with them.)

Everything is drawn on the chip in one jitted call, so a million rows
cost no host time.  Nothing in this module imports the program: the
reference and the system under test both take their data from here.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int, salt: int) -> jax.Array:
    """A PRNG key from any whole-number seed (also beyond 32 bits)."""
    state = np.random.SeedSequence([int(seed), int(salt)]).generate_state(1)
    return jax.random.PRNGKey(int(state[0] >> 1))


def host_rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(salt)])


class Corpus(NamedTuple):
    x: jax.Array          # (n, d) float32 rows; external id == row
    sparse: jax.Array     # (n,) bool: the row is in a family (no core, halo)
    centre: jax.Array     # (d,) the dense core's centre


@functools.partial(jax.jit, static_argnames=("n", "d", "n_clusters",
                                             "n_core", "n_halo", "family"))
def _corpus(key, core_scale, cluster_scale, family_scale, halo_scale, *, n,
            d, n_clusters, n_core, n_halo, family):
    kc, ka, kf, kr, kk, kh, kp = jax.random.split(key, 7)
    centres = jax.random.normal(kc, (n_clusters, d), jnp.float32)
    centres = centres / jnp.linalg.norm(centres, axis=1, keepdims=True)
    n_rest = n - n_core - n_halo
    n_fam = -(-n_rest // family)
    assign = jax.random.randint(ka, (n_fam,), 0, n_clusters)
    fam = centres[assign] + cluster_scale * jax.random.normal(
        kf, (n_fam, d), jnp.float32)
    rest = jnp.repeat(fam, family, axis=0)[:n_rest] + family_scale * \
        jax.random.normal(kr, (n_rest, d), jnp.float32)
    core = centres[0] + core_scale * jax.random.normal(
        kk, (n_core, d), jnp.float32)
    halo = centres[0] + halo_scale * jax.random.normal(
        kh, (n_halo, d), jnp.float32)
    perm = jax.random.permutation(kp, n)
    return (jnp.concatenate([rest, core, halo])[perm], perm < n_rest,
            centres[0])


def corpus(cfg: dict) -> Corpus:
    n, d = int(cfg["rows"]), int(cfg["dim"])
    x, sparse, centre = _corpus(
        key_for(cfg["data_seed"], 0), jnp.float32(cfg["core_scale"]),
        jnp.float32(cfg["cluster_scale"]), jnp.float32(cfg["family_scale"]),
        jnp.float32(cfg["halo_scale"]), n=n, d=d,
        n_clusters=int(cfg["n_clusters"]),
        n_core=int(n * cfg["dense_core_frac"]),
        n_halo=int(n * cfg["halo_frac"]), family=int(cfg["family_size"]))
    return Corpus(x, sparse, centre)


@functools.partial(jax.jit, static_argnames=("n_dense", "n_sparse"))
def _queries(key, x, sparse, centre, core_scale, *, n_dense, n_sparse):
    kd, ks, kn = jax.random.split(key, 3)
    d = x.shape[1]
    dense = centre + core_scale * jax.random.normal(kd, (n_dense, d))
    # sources: uniform draws, the family rows first (about half the rows
    # are, so 8x draws leave room to spare; checked on the host)
    cand = jax.random.randint(ks, (8 * n_sparse,), 0, x.shape[0])
    order = jnp.argsort(~sparse[cand], stable=True)
    src = cand[order[:n_sparse]]
    nudged = x[src] + core_scale * jax.random.normal(kn, (n_sparse, d))
    return dense, nudged, sparse[src]


def query_pools(seed: int, cfg: dict, c: Corpus, n_dense: int,
                n_sparse: int):
    """Host (n_dense, d) fresh dense-core draws and (n_sparse, d) family
    rows of the corpus nudged off their source by ``core_scale`` noise."""
    dense, nudged, ok = _queries(key_for(seed, 1), c.x, c.sparse, c.centre,
                                 jnp.float32(cfg["core_scale"]),
                                 n_dense=int(n_dense), n_sparse=int(n_sparse))
    if not bool(np.all(np.asarray(ok))):
        raise RuntimeError("too few family rows to draw sparse queries")
    return np.asarray(dense), np.asarray(nudged)


def deleted(cfg: dict) -> np.ndarray:
    """Ids deleted by the churn."""
    n = int(cfg["rows"])
    return host_rng(cfg["data_seed"], 2).choice(
        n, size=int(cfg["delete_frac"] * n), replace=False)


def alive_mask(cfg: dict) -> np.ndarray:
    alive = np.ones(int(cfg["rows"]), bool)
    alive[deleted(cfg)] = False
    return alive


@functools.partial(jax.jit, static_argnames=("k",))
def _kth_sq_dist(q, x, alive, *, k):
    def one(qi):
        d2 = jnp.sum(jnp.square(x - qi), axis=-1)
        d2 = jnp.where(alive, d2, jnp.inf)
        return -jax.lax.top_k(-d2, k)[0][k - 1]
    return jax.lax.map(one, q)


def radius(q_sparse: np.ndarray, c: Corpus, alive: np.ndarray,
           rank: int) -> float:
    """Median, over ``q_sparse``, of the L2 distance to the ``rank``-th
    nearest live row (direct differences, float32)."""
    d2 = _kth_sq_dist(jnp.asarray(q_sparse), c.x, jnp.asarray(alive),
                      k=int(rank))
    return float(np.sqrt(np.median(np.asarray(d2))))


KINDS = ("dense", "sparse")


class Inputs(NamedTuple):
    corpus: Corpus
    alive: np.ndarray      # (n,) bool after the churn's deletions
    requests: np.ndarray   # (pool, rows, d) float32, host
    r: float               # the radius every request asks for


def inputs(seed: int, cfg: dict, plan) -> Inputs:
    """The corpus, the radius, and the request pool of one seed.

    Each row of the plan's pool is drawn in turn from its kind's pool:
    ``dense`` a fresh dense-core draw, ``sparse`` a family row of the
    corpus nudged off its source by ``core_scale`` noise.  The radius is
    the median, over ``radius_probe`` further sparse draws, of the
    distance to their ``radius_rank``-th nearest live row: sparse rows
    report tens of their family, many of them near r; dense rows the
    core and about half the halo, many of its rows near r.
    """
    for k in plan.kind_names:
        if k not in KINDS:
            raise ValueError(f"unknown row kind {k!r}; knows {KINDS}")
    c = corpus(cfg)
    alive = alive_mask(cfg)
    _, probe = query_pools(cfg["data_seed"], cfg, c, 1,
                           int(cfg["radius_probe"]))
    r = radius(probe, c, alive, int(cfg["radius_rank"]))
    dense, sparse = query_pools(seed, cfg, c, max(plan.count("dense"), 1),
                                max(plan.count("sparse"), 1))
    src = {"dense": dense, "sparse": sparse}
    req = np.empty(plan.kinds.shape + (dense.shape[1],), np.float32)
    for ki, name in enumerate(plan.kind_names):
        where = plan.kinds == ki
        req[where] = src[name][:int(where.sum())]
    return Inputs(c, alive, req, r)
