"""Kernel micro-benchmarks + fused-vs-composed query-path comparison.

Two sections:

  * legacy micro rows — per-op ref-path timings (CPU) with the derived
    throughput column, unchanged CSV contract.
  * fused scan comparison — for each route (linear, lsh) x metric
    (l2, l1, cosine, hamming), time the fused kernel entry point
    (``ops.fused_linear_scan`` / ``ops.fused_lsh_scan``) against the
    composed pipeline it replaces (pairwise_dist -> compare ->
    broadcast ids; dedupe_sorted -> x[ids] -> rowwise_dist -> compare),
    and price both against the analytic HBM-traffic roofline
    (``launch.roofline.{linear,lsh}_scan_traffic`` over the chip's
    published HBM bandwidth; ``None`` off a TPU — no chip, no peak).

On CPU hosts both variants dispatch to the same jnp oracles, so the
speedup hovers around 1.0 — the figure is meaningful on TPU, where the
fused path deletes the intermediate HBM round-trips the traffic model
counts.  ``--emit BENCH_kernels.json`` writes the machine-readable
results (schema: docs/benchmarks.md); CI asserts the schema and that
every ``fused_speedup_composed`` entry is finite, and only asserts
speedup > 1 on a real TPU backend.
"""
from __future__ import annotations

import argparse
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.core.lsh.tables import build_tables, gather_candidates
from repro.core.search import dedupe_sorted, rowwise_dist
from repro.kernels import ops
from repro.launch import roofline

_METRIC_RADII = {"l2": 7.0, "l1": 60.0, "cosine": 0.3, "hamming": 24.0}


def _micro_rows(rng):
    """Legacy per-op micro benchmarks (ref path on CPU)."""
    x = jnp.asarray(rng.normal(size=(16384, 256)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    rows = []

    for metric in ("l2", "l1", "cosine"):
        f = jax.jit(lambda a, b, m=metric: ops.pairwise_dist(a, b, m))
        t = timed(f, q, x)
        gflops = 2 * q.shape[0] * x.shape[0] * x.shape[1] / t / 1e9
        rows.append((f"dist_{metric}", 1e6 * t, f"{gflops:.1f}GFLOP/s"))

    qc = jnp.asarray(rng.integers(0, 2**32, (256, 2), dtype=np.uint32))
    xc = jnp.asarray(rng.integers(0, 2**32, (16384, 2), dtype=np.uint32))
    f = jax.jit(ops.hamming_dist)
    rows.append(("hamming", 1e6 * timed(f, qc, xc), "64-bit codes"))

    r = jnp.asarray(rng.normal(size=(256, 20 * 24)).astype(np.float32))
    f = jax.jit(lambda a, b: ops.simhash_fingerprint(a, b, L=20, k=24))
    rows.append(("simhash", 1e6 * timed(f, x, r), "L=20 k=24"))

    regs = jnp.asarray(rng.integers(0, 24, (256, 20, 128)), jnp.uint8)
    f = jax.jit(ops.hll_merge_estimate)
    rows.append(("hll_merge", 1e6 * timed(f, regs), "m=128 L=20"))
    return rows


def _composed_linear(q, x, r, metric):
    """The pre-fusion linear route: full distance matrix -> compare."""
    if metric == "hamming":
        dists = ops.hamming_dist(q, x).astype(jnp.float32)
    else:
        dists = ops.pairwise_dist(q, x, metric)
    thresh = ops.metric_radius_transform(metric, r)
    mask = dists <= thresh
    ids = jnp.broadcast_to(
        jnp.arange(x.shape[0], dtype=jnp.int32)[None, :], dists.shape)
    return ids, dists, mask


def _composed_lsh(x, cands, q, r, metric):
    """The pre-fusion LSH verification: dedup -> gather -> rowwise."""
    n = x.shape[0]
    ids, uniq = dedupe_sorted(cands, n)
    rows = x[jnp.clip(ids, 0, n - 1)]
    dists = rowwise_dist(rows, q[:, None, :], metric).astype(jnp.float32)
    thresh = ops.metric_radius_transform(metric, r)
    mask = uniq & (dists <= thresh)
    return ids, dists, mask


def _route_rows(rng, scale: float, peaks) -> Dict[str, Dict[str, float]]:
    """Fused vs composed per route x metric, plus the roofline terms
    (``None`` when ``peaks`` is None)."""
    roof = (lambda b: roofline.scan_memory_seconds(b, peaks)) \
        if peaks is not None else (lambda b: None)
    n = max(int(16384 * scale), 512)
    nq = max(int(256 * scale), 32)
    d, W = 128, 2
    L, B, cap = 8, max(n // 64, 16), 32
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(nq, d)).astype(np.float32))
    xc = jnp.asarray(rng.integers(0, 2**32, (n, W), dtype=np.uint32))
    qc = jnp.asarray(rng.integers(0, 2**32, (nq, W), dtype=np.uint32))
    bids = jnp.asarray(rng.integers(0, B, size=(n, L), dtype=np.int32))
    tables = build_tables(jnp.arange(n, dtype=jnp.int32), bids, B, 16)
    qb = jnp.asarray(rng.integers(0, B, size=(nq, L), dtype=np.int32))
    cands = jax.jit(gather_candidates, static_argnames=("cap", "sentinel"))(
        tables, qb, cap, n)
    c = int(cands.shape[1])

    out: Dict[str, Dict[str, float]] = {}
    for metric in ("l2", "l1", "cosine", "hamming"):
        r = _METRIC_RADII[metric]
        qq, xx = (qc, xc) if metric == "hamming" else (q, x)
        dim = W if metric == "hamming" else d

        fused = jax.jit(lambda a, b, m=metric, rr=r:
                        ops.fused_linear_scan(a, b, rr, m))
        comp = jax.jit(lambda a, b, m=metric, rr=r:
                       _composed_linear(a, b, rr, m))
        tf, tc_ = timed(fused, qq, xx), timed(comp, qq, xx)
        traffic = roofline.linear_scan_traffic(nq, n, dim)
        out[f"linear_{metric}"] = {
            "fused_s": tf, "composed_s": tc_,
            "fused_speedup_composed": tc_ / max(tf, 1e-12),
            "candidates_per_s": nq * n / max(tf, 1e-12),
            "fused_bytes": traffic["fused_bytes"],
            "composed_bytes": traffic["composed_bytes"],
            "roofline_fused_s": roof(traffic["fused_bytes"]),
            "roofline_composed_s": roof(traffic["composed_bytes"]),
        }

        fused = jax.jit(lambda a, cd, b, m=metric, rr=r:
                        ops.fused_lsh_scan(a, jnp.sort(cd, axis=-1), b,
                                           rr, m))
        comp = jax.jit(lambda a, cd, b, m=metric, rr=r:
                       _composed_lsh(a, cd, b, rr, m))
        tf, tc_ = timed(fused, xx, cands, qq), timed(comp, xx, cands, qq)
        traffic = roofline.lsh_scan_traffic(nq, c, dim)
        out[f"lsh_{metric}"] = {
            "fused_s": tf, "composed_s": tc_,
            "fused_speedup_composed": tc_ / max(tf, 1e-12),
            "candidates_per_s": nq * c / max(tf, 1e-12),
            "fused_bytes": traffic["fused_bytes"],
            "composed_bytes": traffic["composed_bytes"],
            "roofline_fused_s": roof(traffic["fused_bytes"]),
            "roofline_composed_s": roof(traffic["composed_bytes"]),
        }
    out["_shapes"] = {"n": n, "nq": nq, "d": d, "candidates": c}
    return out


def main(scale: float | None = None, emit: str | None = None):
    """Print the CSV rows; with ``emit`` also write BENCH_kernels.json."""
    rng = np.random.default_rng(0)
    rows = _micro_rows(rng)
    print("kernel,us_per_call,derived")
    for name, us, derived in rows:
        print(f"kernel_{name},{us:.1f},{derived}")

    if scale is None and emit is None:
        return rows          # legacy benchmarks.run call: micro rows only

    on_tpu = jax.default_backend() == "tpu"
    peaks = (roofline.chip_peaks(jax.devices()[0].device_kind)
             if on_tpu else None)
    routes = _route_rows(rng, scale if scale is not None else 0.12, peaks)
    shapes = routes.pop("_shapes")
    for key, row in sorted(routes.items()):
        roof = row["roofline_fused_s"]
        print(f"kernel_fused_{key},{1e6 * row['fused_s']:.1f},"
              f"{row['fused_speedup_composed']:.2f}x composed; "
              f"{row['candidates_per_s'] / 1e6:.1f}M cand/s; roofline "
              + (f"{1e6 * roof:.1f}us" if roof is not None
                 else "not measured (no chip)"))

    out = {
        "impl": ops.resolve_impl(None),
        "on_tpu": on_tpu,
        "backend": jax.default_backend(),
        "shapes": shapes,
        "hbm_bw": peaks.hbm_bw if peaks is not None else None,
        "routes": routes,
    }
    if emit:
        with open(emit, "w") as f:
            json.dump(out, f, indent=2)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.12)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--emit", default=None)
    args = ap.parse_args()
    main(0.03 if args.quick else args.scale, emit=args.emit)
