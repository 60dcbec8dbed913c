"""Every cell of BENCHMARK.json, found by name and run end to end at a
small size on the CPU: traffic, system, window, reference.  The size is
set here, through ``run_cell``'s ``config``/``traffic``; the benchmark
itself has no size option.  Also: the command refuses to run without a
TPU, and ``correct`` comes out false when the timed path is broken."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import control, faults, harness

SPEC = json.loads((harness.REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**31 + 12345          # seeds beyond 32 bits must work

# small sizes: a few thousand rows, short requests; a wider halo so a
# few dense rows still see rows at the edge of r
SMALL = {"rows": 4096, "insert_rows": 1280, "delta_capacity": 512,
         "radius_probe": 32, "cap": 32, "halo_frac": 0.1}
SMALL_TRAFFIC = {"pool_requests": 8, "rows_per_request": 16,
                 "check_requests": 8}


def small(cell: str):
    c = harness.Cell(cell)
    return dict(c.config, **SMALL), dict(c.traffic, **SMALL_TRAFFIC)


def run_small(cell: str, traced: bool = False):
    cfg, traffic = small(cell)
    return harness.run_cell(cell, SEED, 0.3, traced, config=cfg,
                            traffic=traffic)


def _fails(cell, numbers):
    ref = harness.load_module(harness.Cell(cell).reference_path)
    return [k for k, lim in ref.LIMITS.items() if numbers[k] > lim]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    out = run_small(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {n for n, _ in harness.Cell(cell).metrics(traced=False)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in out["device"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_host_metrics(cell):
    out = run_small(cell, traced=True)
    assert out["correct"]
    # the CPU trace has no device plane: only host-side metrics appear
    for name in ("query_call_ms.index", "extract_ms.index",
                 "frac_linear.index"):
        assert name in out["metrics"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    cfg, traffic = small(cell)
    for seed in (1, 2, 3):
        numbers, _ = control.control_readings(cell, seed, config=cfg,
                                              traffic=traffic)
        assert _fails(cell, numbers)


def test_control_in_linear_rows_alone_is_not_correct():
    """bfloat16 in the linear route's rows alone reads as wrong, through
    the halo rows at the edge of r."""
    cell = [c for c in CELLS if c.endswith(".mixed")][0]
    cfg, traffic = small(cell)
    for seed in (1, 2, 3):
        numbers, tally = control.control_readings(
            cell, seed, config=cfg, traffic=traffic, rows="linear")
        assert "wrong_pairs" in _fails(cell, numbers)
        assert tally["missed_linear"] + tally["outside_or_dead"] > 0


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered",
                                   "lsh_half"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault):
    with faults.planted(fault):
        out = run_small(cell)
    assert not out["correct"]
    if fault == "lsh_half":
        assert out["checks"]["lsh_recall_shortfall"]["value"] > \
            out["checks"]["lsh_recall_shortfall"]["limit"]


def _command(cwd, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_fails_without_tpu():
    p = _command(harness.REPO, CELLS[0])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(harness.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path, CELLS[0])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
