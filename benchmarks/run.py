"""Benchmark orchestrator — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--scale 0.2] [--quick]

Prints ``name,us_per_call,derived`` CSV rows per the harness contract,
plus the per-table CSV blocks.  The roofline report (dry-run derived)
is appended when results/dryrun JSONs exist.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from benchmarks.hostdev import force_host_devices
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.12,
                    help="dataset size fraction of the paper's sizes")
    ap.add_argument("--quick", action="store_true",
                    help="tiny scale for CI (0.03)")
    ap.add_argument("--emit", metavar="PATH", default=None,
                    help="run a streaming benchmark and write its JSON; "
                         "--emit BENCH_streaming.json runs the single-host "
                         "bench, --emit BENCH_sharded.json the mesh-sharded "
                         "one (>= 2 host devices forced under "
                         "JAX_PLATFORMS=cpu), --emit "
                         "BENCH_lsm.json the LSM compaction-stall bench, "
                         "--emit BENCH_async.json the serving-thread stall "
                         "comparison (tick-based vs async CompactionDriver), "
                         "--emit BENCH_rebalance.json the skewed-stream "
                         "placement comparison (ditto), "
                         "--emit BENCH_obs.json the observability overhead "
                         "+ misroute-rate bench, --emit BENCH_kernels.json "
                         "the fused-vs-composed kernel comparison, --emit "
                         "BENCH_serve.json the closed-loop serving "
                         "throughput bench (coalescing + result cache vs "
                         "naive), --emit BENCH_serve_mt.json the multi-"
                         "tenant flood-isolation bench (per-tenant token "
                         "buckets under a noisy neighbor), --emit "
                         "BENCH_recovery.json the checkpoint-stall + "
                         "warm-standby recovery bench (>= 2 host devices "
                         "forced for the elastic restore). Skips the "
                         "paper tables")
    args = ap.parse_args()
    scale = 0.03 if args.quick else args.scale
    emit = os.path.basename(args.emit) if args.emit else ""
    if any(k in emit for k in ("rebalance", "recovery", "sharded")):
        force_host_devices(2)           # before the first jax import
    enable_compile_cache()

    if args.emit and "rebalance" in os.path.basename(args.emit):
        from benchmarks import sharded_bench
        print("name,us_per_call,derived")
        t0 = time.time()
        rows = sharded_bench.skew_main(scale, emit=args.emit)
        print(f"rebalance_p99_keep_local,"
              f"{1e6 * rows['p99_keep_local_s']:.1f},"
              f"linear route; max-shard frac "
              f"{rows['max_shard_frac_keep_local']:.2f}, "
              f"padded rows {rows['sum_n_pad_keep_local']}")
        print(f"rebalance_p99_load_balance,"
              f"{1e6 * rows['p99_load_balance_s']:.1f},"
              f"linear route; max-shard frac "
              f"{rows['max_shard_frac_load_balance']:.2f}, "
              f"padded rows {rows['sum_n_pad_load_balance']} "
              f"({rows['rows_moved_load_balance']} rows moved)")
        print(f"rebalance_skew_latency_delta,"
              f"{1e6 * rows['skew_latency_delta_s']:.1f},"
              f"linear-route p99 cut {rows['p99_keep_local_s'] / max(rows['p99_load_balance_s'], 1e-12):.2f}x; "
              f"padded-rows cut {rows['padded_rows_cut']:.2f}x")
        print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
              f"scale={scale} -> {args.emit}")
        return

    if args.emit and "recovery" in os.path.basename(args.emit):
        from benchmarks import recovery_bench
        print("name,us_per_call,derived")
        t0 = time.time()
        rows = recovery_bench.main(scale, emit=args.emit)
        print(f"recovery_cut_stall,"
              f"{1e6 * rows['cut_checkpoint_stall_s']:.1f},"
              f"consistent-cut incremental snapshot with "
              f"{rows['pending_merges_at_cut']} merges queued "
              f"(flush barrier: "
              f"{1e3 * rows['flush_checkpoint_stall_s']:.1f}ms; "
              f"stall cut {rows['snapshot_stall_cut']:.1f}x)")
        print(f"recovery_incremental_bytes,{0:.1f},"
              f"{rows['incremental_save_bytes']} of "
              f"{rows['full_state_bytes']} state bytes rewritten "
              f"({100 * rows['incremental_bytes_frac']:.1f}%; "
              f"{rows['chunks_reused']} chunks reused)")
        print(f"recovery_restore,"
              f"{1e6 * rows['restore_s']:.1f},"
              f"warm-standby restore, identical="
              f"{rows['restore_identical']}; elastic 2->1 "
              f"{rows['elastic_restore_s']}s, identical="
              f"{rows['elastic_identical']}")
        print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
              f"scale={scale} -> {args.emit}")
        return

    if args.emit and "kernel" in os.path.basename(args.emit):
        from benchmarks import kernel_bench
        t0 = time.time()
        out = kernel_bench.main(scale, emit=args.emit)
        worst = min(r["fused_speedup_composed"]
                    for k, r in out["routes"].items())
        print(f"kernel_fused_min_speedup,{0:.1f},"
              f"{worst:.2f}x composed (impl={out['impl']}, "
              f"tpu={out['on_tpu']})")
        print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
              f"scale={scale} -> {args.emit}")
        return

    # "serve_mt" must dispatch before the "serve" substring check below
    if args.emit and "serve_mt" in os.path.basename(args.emit):
        from benchmarks import serve_bench
        print("name,us_per_call,derived")
        t0 = time.time()
        rows = serve_bench.multi_tenant_main(scale, emit=args.emit)
        print(f"serve_mt_quiet_p99_solo,"
              f"{1e6 * rows['quiet_p99_solo_s']:.0f},"
              f"quiet-tenant p99 with no flood")
        print(f"serve_mt_quiet_p99_flood,"
              f"{1e6 * rows['quiet_p99_flood_s']:.0f},"
              f"isolation ratio {rows['isolation_ratio_p99']:.2f}x "
              f"(no-quota counterfactual "
              f"{rows['noquota_ratio_p99']:.2f}x); flood "
              f"{rows['noisy_rejected']} rejected / "
              f"{rows['noisy_admitted']} admitted at the token bucket")
        print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
              f"scale={scale} -> {args.emit}")
        return

    if args.emit and "serve" in os.path.basename(args.emit):
        from benchmarks import serve_bench
        print("name,us_per_call,derived")
        t0 = time.time()
        rows = serve_bench.main(scale, emit=args.emit)
        for mode in ("naive", "coalesced", "coalesced_cache"):
            m = rows["modes"][mode]
            print(f"serve_sustained_qps_{mode},"
                  f"{1e6 / max(m['sustained_qps'], 1e-9):.1f},"
                  f"{m['sustained_qps']:.0f} qps sustained "
                  f"(p99 {1e3 * m['p99_s_at_sustained']:.1f}ms vs SLO "
                  f"{1e3 * rows['slo_s']:.0f}ms; capacity "
                  f"{m['capacity_qps']:.0f} qps)")
        print(f"serve_speedup_vs_naive,{0:.1f},"
              f"coalesced {rows['speedup_coalesced_vs_naive']:.1f}x, "
              f"+cache {rows['speedup_cache_vs_naive']:.1f}x at "
              f"cold hit rate {rows['cache_hit_rate']:.2f} "
              f"({rows['n_distinct']}/{rows['n_requests']} distinct)")
        print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
              f"scale={scale} -> {args.emit}")
        return

    if args.emit and "obs" in os.path.basename(args.emit):
        from benchmarks import obs_bench
        print("name,us_per_call,derived")
        t0 = time.time()
        rows = obs_bench.main(scale, emit=args.emit)
        print(f"obs_query_disabled,"
              f"{1e6 * rows['query_s_disabled']:.1f},"
              f"per {rows['n_queries']}-query batch, n={rows['n']}")
        print(f"obs_query_enabled,"
              f"{1e6 * rows['query_s_enabled']:.1f},"
              f"overhead {100 * rows['obs_overhead_frac']:.2f}% at "
              f"sample_every={rows['trace_sample_every']} "
              f"(every-batch tracing: "
              f"{100 * rows['trace_overhead_frac']:.1f}%)")
        print(f"obs_misroute_rate,{0:.1f},"
              f"{rows['misroute_rate']:.4f} over {rows['queries_traced']} "
              f"traced queries; frac_lsh {rows['frac_lsh']:.2f}")
        print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
              f"scale={scale} -> {args.emit}")
        return

    if args.emit and "sharded" in os.path.basename(args.emit):
        from benchmarks import sharded_bench
        print("name,us_per_call,derived")
        t0 = time.time()
        rows = sharded_bench.main(scale, emit=args.emit)
        print(f"sharded_churn_throughput,"
              f"{1e6 / max(rows['churn_docs_per_s'], 1e-9):.1f},"
              f"{rows['churn_docs_per_s']:.0f} docs/s over "
              f"{rows['shards']} shards")
        print(f"sharded_query_per_shard,"
              f"{1e6 * rows['query_batch_s_per_shard']:.1f},"
              f"{rows['query_batch_s_per_shard'] / max(rows['query_batch_s_global'], 1e-12):.2f}x global "
              f"(after compact: "
              f"{rows['query_batch_s_after_compact'] / max(rows['query_batch_s_global'], 1e-12):.2f}x)")
        print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
              f"scale={scale} -> {args.emit}")
        return

    if args.emit and "async" in os.path.basename(args.emit):
        from benchmarks import lsm_bench
        print("name,us_per_call,derived")
        t0 = time.time()
        rows = lsm_bench.async_main(scale, emit=args.emit)
        print(f"async_serving_maint_tick,"
              f"{1e6 * rows['serving_maint_s_tick']:.1f},"
              f"serving-thread compaction s over {rows['rounds']} rounds "
              f"(budgeted ticks)")
        print(f"async_serving_maint_driver,"
              f"{1e6 * rows['serving_maint_s_driver']:.1f},"
              f"driver drain() only; {rows['driver_stage_calls']} gathers "
              f"on the worker, {rows['driver_applied']} swaps applied")
        print(f"async_serving_stall_cut,{0:.1f},"
              f"{rows['serving_stall_cut']:.1f}x less serving-thread "
              f"compaction time; round p99 "
              f"{1e3 * rows['driver_round_p99_s']:.1f}ms vs "
              f"{1e3 * rows['tick_round_p99_s']:.1f}ms tick")
        print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
              f"scale={scale} -> {args.emit}")
        return

    if args.emit and "lsm" in os.path.basename(args.emit):
        from benchmarks import lsm_bench
        print("name,us_per_call,derived")
        t0 = time.time()
        rows = lsm_bench.main(scale, emit=args.emit)
        print(f"lsm_round_p99_budgeted,"
              f"{1e6 * rows['budgeted_round_p99_s']:.1f},"
              f"max {1e3 * rows['budgeted_round_max_s']:.1f}ms over "
              f"{rows['n_churn']} churned docs")
        print(f"lsm_stall_cut_vs_monolithic,{0:.1f},"
              f"{rows['stall_cut_vs_monolithic']:.1f}x lower worst-case "
              f"query-batch stall (vs sync tiered: "
              f"{rows['stall_cut_vs_sync']:.1f}x)")
        print(f"lsm_insert_throughput,"
              f"{1e6 / max(rows['insert_docs_per_s'], 1e-9):.1f},"
              f"{rows['insert_docs_per_s']:.0f} docs/s; merges/level "
              f"{rows['budgeted_merges_per_level']}")
        print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
              f"scale={scale} -> {args.emit}")
        return

    if args.emit:
        from benchmarks import streaming_bench
        print("name,us_per_call,derived")
        t0 = time.time()
        rows = streaming_bench.main(scale, emit=args.emit)
        print(f"streaming_insert_throughput,"
              f"{1e6 / max(rows['insert_docs_per_s'], 1e-9):.1f},"
              f"{rows['insert_docs_per_s']:.0f} docs/s")
        print(f"streaming_insert_vs_rebuild,{0:.1f},"
              f"{rows['speedup_insert_vs_rebuild']:.1f}x faster than "
              f"full rebuild of n={rows['n']}+{rows['n_insert']}")
        print(f"streaming_query_overhead,"
              f"{1e6 * rows['query_batch_s_dynamic']:.1f},"
              f"{rows['query_batch_s_dynamic'] / max(rows['query_batch_s_static'], 1e-12):.2f}x static "
              f"(after compact: "
              f"{rows['query_batch_s_after_compact'] / max(rows['query_batch_s_static'], 1e-12):.2f}x)")
        print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
              f"scale={scale} -> {args.emit}")
        return

    from benchmarks import fig2_hybrid, fig3_output, kernel_bench, table1_hll
    from benchmarks import roofline_report

    print("name,us_per_call,derived")
    t0 = time.time()
    kernel_bench.main()

    rows1 = table1_hll.main(scale)
    mean_err = sum(r["pct_error"] for r in rows1) / len(rows1)
    print(f"table1_mean_hll_error,{0:.1f},{mean_err:.2f}%"
          f" (paper: <7%; theory m=128: 9.2%)")

    rows2 = fig2_hybrid.main(scale)
    vs_lsh = sum(1 for r in rows2 if r["hybrid_s"] <= 1.1 * r["lsh_s"])
    near_best = sum(1 for r in rows2 if r["hybrid_s"] <= max(
        2.0 * min(r["lsh_s"], r["linear_s"]),
        min(r["lsh_s"], r["linear_s"]) + 0.01))
    print(f"fig2_hybrid_vs_lsh,{0:.1f},{vs_lsh}/{len(rows2)} radii with "
          f"hybrid <= 1.1x LSH-only (paper: hybrid never loses to LSH)")
    print(f"fig2_hybrid_near_best,{0:.1f},{near_best}/{len(rows2)} radii "
          f"with hybrid within 2x/10ms of best single strategy")

    rows3 = fig3_output.main(scale)
    mono = all(rows3[i]["pct_linear_calls"] <= rows3[i + 1]
               ["pct_linear_calls"] + 1e-9 for i in range(len(rows3) - 1))
    print(f"fig3_linear_calls_monotone,{0:.1f},{mono}")

    try:
        roofline_report.main()
    except Exception as e:  # dry-run results may not exist yet
        print(f"roofline_report,0.0,skipped ({e})")
    print(f"total_bench_seconds,{1e6*(time.time()-t0):.0f},"
          f"scale={scale}")


if __name__ == "__main__":
    main()
