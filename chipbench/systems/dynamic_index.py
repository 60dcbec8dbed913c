"""System under test: ``DynamicHybridIndex`` through its library entry.

A request is a batch of query rows.  The caller holds it in host memory,
calls ``index.query(q, r)`` (estimate, route, search on the device) and
then ``QueryResult.reported(i)`` for every row, which brings that row's
ids and distances to the host.  The request is done when every row's
answer is on the host.

Set-up follows ``chip_smoke.churn``: build on all but ``insert_rows``
rows, insert those (level-0 freezes), delete ``delete_frac`` of the ids.
External id == row of the corpus.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import clustered
from repro.core.engine import partition_indices
from repro.core.lsh import make_family
from repro.streaming import DynamicHybridIndex


def _num_buckets(n: int) -> int:
    """Power-of-two bucket space, ~8 rows per bucket (as bring-up)."""
    return 1 << max(int(np.ceil(np.log2(n / 8))), 4)


@functools.partial(jax.jit, static_argnames=("cap",))
def _distinct_candidates(perm, starts, qb, *, cap):
    """(Q,) distinct ids among the first ``cap`` of each probed bucket:
    the rows the LSH route has to verify (CSR tables, one per column)."""
    n_tables, n = perm.shape
    b = qb.astype(jnp.int32)
    t = jnp.arange(n_tables)[None, :]
    lo = starts[t, b]
    size = starts[t, b + 1] - lo
    offs = jnp.arange(cap, dtype=jnp.int32)
    got = perm[t[..., None], jnp.clip(lo[..., None] + offs, 0, n - 1)]
    ids = jnp.where(offs < size[..., None], got, n).reshape(b.shape[0], -1)
    s = jnp.sort(ids, axis=-1)
    first = jnp.concatenate([s[:, :1] < n, (s[:, 1:] != s[:, :-1])
                             & (s[:, 1:] < n)], axis=-1)
    return jnp.sum(first, axis=-1, dtype=jnp.int32)


class Inputs(NamedTuple):
    requests: np.ndarray        # (pool, rows, d) float32, host
    r: float                    # the radius every request asks for
    linear: np.ndarray          # (pool, rows) bool, see ``inputs``
    corpus: clustered.Corpus


def inputs(seed: int, cfg: Dict, plan) -> Inputs:
    """The request pool, the radius and the corpus of one seed
    (``clustered.inputs``), and ``linear``: the rows the control answers
    as the linear route would serve them, the ``dense`` kind (the router
    sends every dense row of ``data_seed`` 0 there), the others as the
    LSH route."""
    inp = clustered.inputs(seed, cfg, plan)
    dense = (plan.kind_names.index("dense") if "dense" in plan.kind_names
             else -1)
    return Inputs(inp.requests, inp.r, plan.kinds == dense, inp.corpus)


class System:
    """The index, the request pool and the radius of one seed."""

    def __init__(self, cfg: Dict, seed: int, plan, log=None):
        self.cfg = cfg
        log = log or (lambda msg: None)
        t = time.perf_counter()

        def phase(name):
            nonlocal t
            log(f"  set-up {name}: {time.perf_counter() - t:.3f} s")
            t = time.perf_counter()

        n, d = int(cfg["rows"]), int(cfg["dim"])
        inp = inputs(seed, cfg, plan)
        self.r, self.requests = inp.r, inp.requests
        x = np.asarray(inp.corpus.x)
        del inp
        phase("corpus, queries, radius")
        fam = make_family(cfg["metric"], d=d, L=int(cfg["tables"]), r=self.r,
                          k=int(cfg["lsh_k"]),
                          w=float(cfg["lsh_w_over_r"]) * self.r)
        self.index = DynamicHybridIndex(
            fam, num_buckets=_num_buckets(n), m=int(cfg["hll_m"]),
            cap=int(cfg["cap"]), delta_capacity=int(cfg["delta_capacity"]),
            key=int(clustered.host_rng(cfg["data_seed"], 4).integers(
                2**31 - 1)))
        n0 = n - int(cfg["insert_rows"])
        self.index.build(x[:n0])
        phase("build")
        self.index.insert(jnp.asarray(x[n0:]))
        phase("insert")
        gone = clustered.deleted(cfg)
        removed = self.index.delete(gone.tolist())
        if removed != len(gone):
            raise RuntimeError(f"deleted {removed} of {len(gone)} ids")
        phase("delete")

    # ------------------------------------------------------------ serving
    def serve(self, i: int, rec):
        """Request ``i``: query, then every row's answer to the host.
        Returns (ids per row, counts of this request)."""
        q = self.requests[i % len(self.requests)]
        with rec.span("query"):
            res = self.index.query(q, self.r)
        with rec.span("extract"):
            out = [res.reported(j)[0] for j in range(q.shape[0])]
        lin = np.zeros(q.shape[0], bool)
        lin[np.asarray(res.lin_idx, np.int64)] = True
        return out, {"rows": q.shape[0], "linear": lin,
                     "linear_pairs": int(sum(len(out[j])
                                             for j in np.nonzero(lin)[0]))}

    def warmup(self, rec) -> int:
        """Serve one request of each (linear, LSH) group shape the pool
        produces, as the index routes it; returns how many were served."""
        q = self.requests.reshape(-1, self.requests.shape[-1])
        use_lsh = np.asarray(self.index.estimate(jnp.asarray(q)).use_lsh)
        use_lsh = use_lsh.reshape(self.requests.shape[:2])
        seen = {}
        for i, u in enumerate(use_lsh):
            lsh_idx, lin_idx = partition_indices(u)
            seen.setdefault((len(lsh_idx), len(lin_idx)), i)
        for i in seen.values():
            self.serve(i, rec)
        return len(seen)

    # ----------------------------------------------------------- counting
    def work_counts(self, served: List) -> Dict[str, float]:
        """The algorithm's work over ``served`` [(request, counts)]: rows
        per route, the rows each linear call compares against (the live
        rows), reported linear pairs, and the distinct candidates the LSH
        route verifies in the frozen segments (cap-truncated)."""
        idx = self.index
        lsh_q = [self.requests[i % len(self.requests)][~c["linear"]]
                 for i, c in served]
        lsh_q = np.concatenate(lsh_q) if lsh_q else np.zeros((0, 1))
        cand = 0
        for lo in range(0, lsh_q.shape[0], 4096):
            qb = idx.family.bucket_ids(idx.params,
                                       jnp.asarray(lsh_q[lo:lo + 4096]),
                                       idx.num_buckets)
            for f in idx.stack.segments:
                t = f.seg.tables
                cand += int(jnp.sum(_distinct_candidates(
                    t.perm, t.starts, qb, cap=idx.cap)))
        return {
            "dim": float(self.cfg["dim"]),
            "live_rows": float(idx.n),
            "linear_calls": float(sum(bool(c["linear"].any())
                                      for _, c in served)),
            "linear_rows": float(sum(int(c["linear"].sum())
                                     for _, c in served)),
            "linear_pairs": float(sum(c["linear_pairs"] for _, c in served)),
            "lsh_rows": float(lsh_q.shape[0]),
            "lsh_candidates": float(cand),
        }

    def close(self) -> None:
        self.index = None
