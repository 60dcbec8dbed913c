"""Every cell of BENCHMARK.json, found by name and run end to end at a
small size on the CPU: traffic, system, window, reference.  The size is
the configuration's ``configs/<config>.small.json``, passed through
``run_cell``'s ``config``/``traffic``; the benchmark itself has no size
option.  Also: the command refuses to run without a TPU, ``correct``
comes out false when the timed path is broken, and every configuration
brings the files the harness finds by its name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import control, faults, harness

SPEC = json.loads((harness.REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
CONFIGS = [c["name"] for c in SPEC["configs"]]
SEED = 2**31 + 12345          # seeds beyond 32 bits must work


def small_path(config: str):
    return harness.BENCH / "configs" / f"{config}.small.json"


def small(cell: str):
    """The cell's configuration and traffic with the overrides of its
    configuration's ``.small.json``."""
    c = harness.Cell(cell)
    over = json.loads(small_path(c.workload["config"]).read_text())
    return dict(c.config, **over["config"]), dict(c.traffic,
                                                  **over["traffic"])


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
    # the CPU trace has no device plane: every per-layer metric of the
    # cell but those read from the device trace appears, and no device
    # number
    source = {m["name"]: m["source"] for m in SPEC["per_layer"]}
    want = {n for n, _ in harness.Cell(cell).metrics(traced=True)
            if source[n] != "device_trace"}
    assert set(out["metrics"]) == want
    assert "busy_s" not in out["device"] and "breakdown" not in out


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    cfg, traffic = small(cell)
    for seed in (1, 2, 3):
        numbers, _ = control.control_readings(cell, seed, config=cfg,
                                              traffic=traffic)
        assert _fails(cell, numbers)


# the control's numbers and tallies on seed 1 at the small size, as the
# control read them when it drew its inputs with ``clustered.inputs``
# itself; the adapters' ``inputs`` must give the same, seed for seed
CONTROL_SEED1 = {
    ("hybridlsh-densecore-l2.mixed", "all"): (
        {"wrong_pairs": 66.0, "lsh_recall_shortfall": 0.0},
        {"reported": 144061, "reference": 144054, "band": 37, "bad_id": 0,
         "duplicate": 0, "outside_or_dead": 33, "missed_linear": 33,
         "missed_deep": 0, "lsh_within": 1989, "lsh_found": 1978,
         "lsh_short": 0, "rows": 128}),
    ("hybridlsh-densecore-l2.mixed", "linear"): (
        {"wrong_pairs": 55.0, "lsh_recall_shortfall": 0.0},
        {"reported": 144059, "reference": 144054, "band": 37, "bad_id": 0,
         "duplicate": 0, "outside_or_dead": 22, "missed_linear": 33,
         "missed_deep": 0, "lsh_within": 1989, "lsh_found": 1989,
         "lsh_short": 0, "rows": 128}),
    ("hybridlsh-densecore-l2.sparse", "all"): (
        {"wrong_pairs": 20.0, "lsh_recall_shortfall": 0.0},
        {"reported": 3755, "reference": 3731, "band": 48, "bad_id": 0,
         "duplicate": 0, "outside_or_dead": 20, "missed_linear": 0,
         "missed_deep": 0, "lsh_within": 3731, "lsh_found": 3708,
         "lsh_short": 0, "rows": 128}),
    ("hybridlsh-densecore-l2.sparse", "linear"): (
        {"wrong_pairs": 0.0, "lsh_recall_shortfall": 0.0},
        {"reported": 3756, "reference": 3731, "band": 48, "bad_id": 0,
         "duplicate": 0, "outside_or_dead": 0, "missed_linear": 0,
         "missed_deep": 0, "lsh_within": 3731, "lsh_found": 3731,
         "lsh_short": 0, "rows": 128}),
}


@pytest.mark.parametrize("cell,rows", sorted(CONTROL_SEED1))
def test_control_reads_as_before_the_adapter_drew_its_inputs(cell, rows):
    cfg, traffic = small(cell)
    got = control.control_readings(cell, 1, config=cfg, traffic=traffic,
                                   rows=rows)
    assert got == CONTROL_SEED1[(cell, rows)]


@pytest.mark.parametrize("config", CONFIGS)
def test_every_configuration_brings_its_files(config):
    """A configuration is added as files found by its name: its sizes,
    its reference, its system adapter with ``inputs``, its small size."""
    entry = [c for c in SPEC["configs"] if c["name"] == config][0]
    cfg = json.loads((harness.REPO / entry["file"]).read_text())
    ref = harness.load_module(harness.BENCH / "configs"
                              / f"{config}.reference.py")
    assert callable(ref.check) and ref.LIMITS
    system = harness.load_module(harness.BENCH / "systems"
                                 / f"{cfg['system']}.py")
    assert callable(system.inputs) and callable(system.System)
    over = json.loads(small_path(config).read_text())
    assert set(over) - {"why"} == {"config", "traffic"}
    assert set(over["config"]) <= set(cfg)


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
