"""Sharded streaming benchmark: churn throughput + routing-policy latency.

Measures, at a given ``--scale``, on a host-platform CPU mesh (the
driver forces >= 2 devices via XLA_FLAGS before jax import):

  * churn throughput — steady-state mixed insert/delete batches into the
    per-shard delta segments / tombstone bitmaps (docs/s)
  * query latency under both routing policies ("global" vs "per_shard")
    on the churned index, and again after per-shard compaction
  * compaction cost (per-shard build_tables rebuild)

``--skew`` (``skew_main``) instead drives a *skewed* insert stream (all
rows pinned to shard 0, the key-hash-placement failure mode) through
identical indexes under ``keep_local`` vs ``load_balance`` merge-time
placement, and reports p50/p99 query-batch latency for each.  With
keep_local the hoarding shard pins every level's common ``n_pad`` (all
shards pad to the max shard's rows), so all shards pay its scan cost;
load_balance water-fills rows across shards at each merge, halving (at
S=2) the padded rows per shard.  The gated latencies are measured on
the linear route — the one Eq. 2 prices at the padded scan size, i.e.
the cost term skew actually inflates (the LSH route's cap-bounded
gathers are padded-size independent; its hybrid numbers are emitted as
``p*_hybrid_*`` context).  Emitted as BENCH_rebalance.json; CI asserts
the p99 delta is non-negative and the padded-row cut is real.

Emits a JSON blob (``--emit``) so the sharded perf trajectory is
tracked alongside BENCH_streaming.json.
"""
from __future__ import annotations

import json
import time
from typing import Dict

if __name__ == "__main__":
    # before jax is imported: XLA reads the device count at start-up
    from benchmarks.hostdev import force_host_devices
    force_host_devices(2)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CostModel
from repro.core.lsh import make_family
from repro.data import clustered_dataset
from repro.streaming import CompactionPolicy, ShardedDynamicHybridIndex


def main(scale: float = 0.12, emit: str | None = None) -> Dict[str, float]:
    n = max(2000, int(50000 * scale))
    n_churn = max(256, n // 8)
    d, L, B, m, r = 16, 8, 1024, 64, 1.2
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    shards = mesh.shape["data"]
    x = np.asarray(clustered_dataset(n + n_churn, d, n_clusters=32,
                                     dense_core_frac=0.2, core_scale=0.05,
                                     seed=0, metric="l2"), np.float32)
    rng = np.random.default_rng(0)
    q = x[rng.integers(0, n, 64)]
    fam = make_family("l2", d=d, L=L, r=1.0)

    def build(routing):
        idx = ShardedDynamicHybridIndex(
            fam, num_buckets=B, mesh=mesh, m=m, cap=256,
            delta_capacity=max(1024, n_churn),
            cost_model=CostModel(alpha=1.0, beta=10.0),
            policy=CompactionPolicy(delta_fill=2.0, tombstone_ratio=2.0),
            routing=routing, max_out=256, key=0)
        idx.build(x[:n])
        return idx

    ins_batch, del_batch = 64, 32

    def churn(i, timed):
        """Identical mixed insert/delete stream; optionally timed."""
        t0 = time.perf_counter()
        ops = 0
        for lo in range(n + 64, n + n_churn, ins_batch):
            take = min(ins_batch, n + n_churn - lo)
            i.insert(x[lo:lo + take])
            i.delete(range(lo - n, lo - n + del_batch))
            ops += take + del_batch
        return ops, (time.perf_counter() - t0) if timed else 0.0

    idx = build("per_shard")
    # warm the mutation + query paths (jit compile)
    idx.insert(x[n:n + 64])
    idx.delete(range(0, 32))
    idx.query(jnp.asarray(q), r)
    ops, churn_s = churn(idx, timed=True)

    def time_query(i, iters=5):
        i.query(jnp.asarray(q), r)            # warm
        t0 = time.perf_counter()
        for _ in range(iters):
            i.query(jnp.asarray(q), r)
        return (time.perf_counter() - t0) / iters

    q_per_shard = time_query(idx)

    # same corpus through the same churn, global routing: the latency
    # ratio isolates the policy, not churn state
    glob = build("global")
    glob.insert(x[n:n + 64])
    glob.delete(range(0, 32))
    churn(glob, timed=False)
    q_global = time_query(glob)

    t0 = time.perf_counter()
    idx.compact()
    compact_s = time.perf_counter() - t0
    q_after = time_query(idx)
    st = idx.index_stats()

    out = {
        "n": n, "n_churn_ops": ops, "shards": int(shards), "queries": 64,
        "churn_docs_per_s": ops / max(churn_s, 1e-9),
        "churn_total_s": churn_s,
        "query_batch_s_per_shard": q_per_shard,
        "query_batch_s_global": q_global,
        "query_batch_s_after_compact": q_after,
        "compact_s": compact_s,
        "compact_total_s": st["total_seconds"],
        "n_live": st["n_live"],
    }
    if emit:
        with open(emit, "w") as f:
            json.dump(out, f, indent=2)
    return out


def skew_main(scale: float = 0.12,
              emit: str | None = None) -> Dict[str, float]:
    """Skewed-stream placement comparison (see module docstring).

    The CI-gated latencies (``p50/p99_{placement}_s``,
    ``skew_latency_delta_s``) are measured on the *linear route*
    (``force="linear"``): Eq. 2 prices that route at the padded scan
    size, which is exactly the term a hoarding shard inflates — and
    which the router's estimate therefore sees for every query.  The
    hybrid route's numbers ride along as ``p*_hybrid_*`` for context;
    when it picks LSH (cap-bounded bucket gathers, padded-size
    independent) the placements tie, which is itself the router working
    as designed.
    """
    n = max(6000, int(50000 * scale))
    d, L, B, m = 32, 8, 1024, 64
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    shards = int(mesh.shape["data"])
    x = np.asarray(clustered_dataset(n, d, n_clusters=32,
                                     dense_core_frac=0.2, core_scale=0.05,
                                     seed=0, metric="l2"), np.float32)
    rng = np.random.default_rng(0)
    q = jnp.asarray(x[rng.integers(0, n, 64)])
    fam = make_family("l2", d=d, L=L, r=1.0)
    r = 1.2
    cap = max(512, n // 4)

    def build(placement):
        idx = ShardedDynamicHybridIndex(
            fam, num_buckets=B, mesh=mesh, m=m, cap=256, delta_capacity=cap,
            cost_model=CostModel(alpha=1.0, beta=10.0),
            policy=CompactionPolicy(delta_fill=1.0, tombstone_ratio=2.0,
                                    fanout=2, step_rows=None),
            placement=placement, routing="global", max_out=256, key=0)
        # the skewed stream: every insert batch pinned to shard 0
        # (key-hash placement); merges drain synchronously, so each
        # placement policy's steady state is what queries see
        for lo in range(0, n, 512):
            idx.insert(x[lo:lo + 512], shard=0)
        return idx

    placements = ("keep_local", "load_balance")
    out: Dict[str, float] = {"n": n, "shards": shards, "queries": 64,
                             "measured_route": "linear"}
    idxs = {}
    for placement in placements:
        idx = build(placement)
        idx.query(q, r, force="linear")              # warm (jit compile)
        hyb = idx.query(q, r)
        out[f"frac_lsh_hybrid_{placement}"] = float(
            np.asarray(hyb.used_lsh).mean())
        idxs[placement] = idx
        st = idx.index_stats()
        loads = np.asarray(st["live_per_shard"]) + np.asarray(
            st["delta_per_shard"])
        out[f"sum_n_pad_{placement}"] = int(sum(st["level_n_pads"]))
        out[f"max_shard_frac_{placement}"] = float(
            loads.max() / max(loads.sum(), 1))
        out[f"rows_moved_{placement}"] = int(st["rows_moved"])
        out[f"shard_skew_{placement}"] = float(st["shard_skew"])

    # interleave the timed runs so ambient noise (CI runner hiccups, GC
    # pauses) lands on both placements alike instead of biasing one.
    # p99 is the MIN of per-round p99s: external contamination can only
    # inflate a round's tail (p99 of 25 samples is essentially its max),
    # so the least-contaminated round is the best available observation
    # of the workload's own tail — a shared-runner hiccup in one or two
    # rounds cannot flip the sign of the CI-gated delta
    def measure(force, rounds=3, iters=25):
        lat: Dict[str, list] = {p: [] for p in placements}
        for _ in range(rounds):
            rd: Dict[str, list] = {p: [] for p in placements}
            for _ in range(iters):
                for placement in placements:
                    t0 = time.perf_counter()
                    idxs[placement].query(q, r, force=force)
                    rd[placement].append(time.perf_counter() - t0)
            for placement in placements:
                lat[placement].append(np.asarray(rd[placement]))
        return {p: (float(np.quantile(np.concatenate(s), 0.5)),
                    float(min(np.quantile(x_, 0.99) for x_ in s)))
                for p, s in lat.items()}

    linear = measure("linear")
    hybrid = measure(None, rounds=1)
    for placement in placements:
        out[f"p50_{placement}_s"], out[f"p99_{placement}_s"] = \
            linear[placement]
        (out[f"p50_hybrid_{placement}_s"],
         out[f"p99_hybrid_{placement}_s"]) = hybrid[placement]
    out["skew_latency_delta_s"] = (out["p99_keep_local_s"]
                                   - out["p99_load_balance_s"])
    out["skew_p50_delta_s"] = (out["p50_keep_local_s"]
                               - out["p50_load_balance_s"])
    out["padded_rows_cut"] = (out["sum_n_pad_keep_local"]
                              / max(out["sum_n_pad_load_balance"], 1))
    if emit:
        with open(emit, "w") as f:
            json.dump(out, f, indent=2)
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--skew", action="store_true",
                    help="run the skewed-stream placement comparison "
                         "(keep_local vs load_balance) instead of the "
                         "churn/routing bench")
    ap.add_argument("--scale", type=float, default=0.12)
    ap.add_argument("--emit", metavar="PATH", default=None)
    args = ap.parse_args()
    if len(jax.devices()) < 2:
        raise SystemExit("the sharded bench needs >= 2 devices: run it "
                         "with JAX_PLATFORMS=cpu (two host devices are "
                         "forced) or on a multi-chip host")
    run = skew_main if args.skew else main
    print(json.dumps(run(args.scale, emit=args.emit), indent=2))
