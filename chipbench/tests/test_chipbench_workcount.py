"""The work counts behind the roofline shares, on hand-worked cases."""
import inspect
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness

METRICS = Path(harness.BENCH) / "metrics"
ROOFLINES = ["linear_scan_roofline", "lsh_scan_roofline"]
# what describes an implementation, not the r-NN work
IMPLEMENTATION_WORDS = ("tile", "slab", "pad", "chunk", "buffer", "block",
                        "slot", "width", "reread", "kernel")


def _metric(name):
    return harness.load_module(METRICS / f"{name}.py")


def test_linear_work_hand_worked():
    # 1 call, 2 query rows against 3 live rows of 4 float32, 5 pairs out:
    # 2*2*3*4 FLOPs; rows 3*4*4 + queries 2*4*4 + pairs 5*(4 + 4) bytes
    flops, nbytes = _metric("linear_scan_roofline").work(
        calls=1, rows=2, live_rows=3, dim=4, reported_pairs=5)
    assert flops == 48
    assert nbytes == 48 + 32 + 40


def test_linear_work_reads_live_rows_once_per_call():
    m = _metric("linear_scan_roofline")
    one = m.work(calls=1, rows=8, live_rows=100, dim=16, reported_pairs=0)
    two = m.work(calls=2, rows=8, live_rows=100, dim=16, reported_pairs=0)
    assert two[0] == one[0]
    assert two[1] - one[1] == 100 * 16 * 4


def test_lsh_work_hand_worked():
    # 10 distinct candidates of 2 query rows, 4 float32 each:
    # 2*10*4 FLOPs; candidates 10*(16 + 4) + queries 2*16 bytes
    flops, nbytes = _metric("lsh_scan_roofline").work(
        candidates=10, rows=2, dim=4)
    assert flops == 80
    assert nbytes == 200 + 32


@pytest.mark.parametrize("name", ROOFLINES)
def test_work_signature_takes_no_implementation_parameter(name):
    params = inspect.signature(_metric(name).work).parameters
    for p in params:
        assert not any(w in p.lower() for w in IMPLEMENTATION_WORDS), p


@pytest.mark.parametrize("name", ROOFLINES)
def test_roofline_reads_nothing_without_trace_or_work(name):
    m = _metric(name)
    ctx = SimpleNamespace(trace=None, work={}, peaks=None, log=print)
    assert m.read(ctx) is None


class _Trace:
    def __init__(self, seconds):
        self.seconds = seconds

    def kernel_seconds(self, names):
        return self.seconds


def test_roofline_share_memory_bound():
    peaks = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    work = {"linear_calls": 1, "linear_rows": 16, "live_rows": 1e6,
            "dim": 128, "linear_pairs": 8e6, "lsh_rows": 16,
            "lsh_candidates": 1e4}
    ctx = SimpleNamespace(trace=_Trace(1e-3), work=work, peaks=peaks,
                          log=lambda *_: None)
    lin = _metric("linear_scan_roofline").read(ctx)
    nbytes = 1e6 * 128 * 4 + 16 * 128 * 4 + 8e6 * 8
    assert lin == pytest.approx(100 * nbytes / 819e9 / 1e-3)
    lsh = _metric("lsh_scan_roofline").read(ctx)
    assert lsh == pytest.approx(100 * (1e4 * 516 + 16 * 512) / 819e9 / 1e-3)
    ctx.trace = _Trace(0.0)
    assert _metric("linear_scan_roofline").read(ctx) is None


def test_distinct_candidates_hand_built_tables():
    di = harness.load_module(harness.BENCH / "systems" / "dynamic_index.py")
    # two tables over 6 rows, 3 buckets each (CSR: perm sorted by bucket)
    perm = jnp.array([[0, 3, 1, 4, 5, 2],
                      [2, 5, 0, 1, 3, 4]], jnp.int32)
    starts = jnp.array([[0, 2, 5, 6],
                        [0, 2, 2, 6]], jnp.int32)
    # query 0: table 0 bucket 1 -> {1, 4, 5}; table 1 bucket 2 -> {0, 1, 3, 4}
    # query 1: table 0 bucket 0 -> {0, 3};    table 1 bucket 1 -> {}
    qb = jnp.array([[1, 2], [0, 1]], jnp.int32)
    got = np.asarray(di._distinct_candidates(perm, starts, qb, cap=8))
    assert got.tolist() == [5, 2]
    # cap 2 keeps the first two ids of each bucket: {1, 4} | {0, 1} -> 3
    got = np.asarray(di._distinct_candidates(perm, starts, qb, cap=2))
    assert got.tolist() == [3, 2]


def test_peaks_table_has_v5e_and_no_default():
    p = harness.peaks_for("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        harness.peaks_for("cpu")
