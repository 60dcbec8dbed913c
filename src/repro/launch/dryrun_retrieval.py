import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Must precede any jax import (device count locks at first init).

"""Dry-run of the PAPER'S OWN workload on the production mesh: the
distributed hybrid query (Algorithm 2 with pmax-merged HLLs and
per-shard routing) over a 134M-vector corpus, lowered + compiled for
the 16x16 (and optionally 2x16x16) mesh with abstract inputs.

  PYTHONPATH=src python -m repro.launch.dryrun_retrieval [--multi-pod]

This proves the retrieval layer itself (not just the LM cells) shards:
the candSize estimate is one (Q, m) pmax; collisions one (Q,) psum;
each shard routes independently and reports a fixed-size union slice.
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.cost_model import CostModel
from repro.core.distributed import ShardedIndexState, make_query_fn
from repro.core.lsh import make_family
from repro.launch import hlo_analysis
from repro.launch import roofline as rl
from repro.launch.dryrun import RESULTS_DIR, _mem_stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--n-total", type=int, default=1 << 27)  # 134M vectors
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--queries", type=int, default=1024)
    args = ap.parse_args()

    from repro.launch.mesh import make_production_mesh
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    # flatten pod+data into the index's data axis if multi-pod
    data_axis = "data"
    shards = mesh.shape[data_axis]
    chips = 1
    for s in mesh.shape.values():
        chips *= s

    n, d, q = args.n_total, args.d, args.queries
    n_local = n // shards
    L, B, m, cap, max_out = 20, 1 << 18, 64, 128, 256
    fam = make_family("cosine", d=d, L=L, r=0.3, delta=0.1)
    params = jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0)))

    sds = jax.ShapeDtypeStruct
    state = ShardedIndexState(
        x=sds((n, d), jnp.float32),
        perm=sds((shards, L, n_local), jnp.int32),
        starts=sds((shards, L, B + 1), jnp.int32),
        registers=sds((shards, L, B, m), jnp.uint8),
    )
    queries = sds((q, d), jnp.float32)

    qfn = make_query_fn(fam, num_buckets=B, mesh=mesh, n_total=n,
                        cost_model=CostModel(1.0, 10.0), metric="cosine",
                        cap=cap, max_out=max_out, policy="per_shard")

    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    state_sh = ShardedIndexState(
        x=sh(data_axis), perm=sh(data_axis), starts=sh(data_axis),
        registers=sh(data_axis))
    params_sh = jax.tree_util.tree_map(lambda _: sh(), params)

    t0 = time.time()
    with mesh:
        lowered = jax.jit(
            lambda st, pa, qq: qfn(st, pa, qq, 0.3),
            in_shardings=(state_sh, params_sh, sh()),
        ).lower(state, params, queries)
        compiled = lowered.compile()
    dt = time.time() - t0

    mem = _mem_stats(compiled)
    costs = hlo_analysis.analyze_text(compiled.as_text())
    wire = sum(costs.wire.values())
    terms = rl.terms_from_cost(
        {"flops": costs.flops, "bytes accessed": costs.bytes}, wire,
        2.0 * q * n * d, chips,  # useful = one full scan equivalent
        rl.chip_peaks(rl.V5E))
    rec = {
        "arch": "paper-hybrid-lsh-index", "shape": f"n={n},d={d},Q={q}",
        "mesh": "2x16x16" if args.multi_pod else "16x16", "tag": "",
        "status": "ok", "chips": chips, "compile_s": round(dt, 1),
        "memory": mem,
        "cost": {"flops": costs.flops, "bytes accessed": costs.bytes},
        "collectives": dict(costs.wire),
        "terms": {"compute_s": terms.compute_s,
                  "memory_s": terms.memory_s,
                  "collective_s": terms.collective_s,
                  "dominant": terms.dominant},
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(
        RESULTS_DIR,
        f"paper-index__retrieval__{rec['mesh']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec["terms"], indent=1))
    print("memory/dev GiB:",
          mem.get("total_bytes_per_device", 0) / 2**30)
    print("compile_s:", dt)


if __name__ == "__main__":
    main()
