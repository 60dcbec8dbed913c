"""Trace reduction: busy and idle share, kernel time by stored name, and
idle gaps labelled by harness span."""
import types
from pathlib import Path

import pytest

from chipbench import harness
from chipbench import trace as tl

DEV, HOST = "/device:TPU:0", "/host:CPU"
DATA = Path(__file__).parent / "data"
RECORDED = DATA / "trace_mixed_small.json.gz"


def ev(plane, line, name, start, end):
    return tl.Event(plane, line, name, float(start), float(end - start))


def hand_made():
    return [
        ev(HOST, "python", "chipbench.request", 0, 100),
        ev(HOST, "python", "chipbench.query", 0, 40),
        ev(HOST, "python", "chipbench.extract", 40, 100),
        ev(HOST, "python", "chipbench.request", 110, 200),
        ev(HOST, "python", "chipbench.query", 110, 150),
        ev(HOST, "python", "chipbench.extract", 150, 200),
        ev(DEV, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
           10, 20),
        ev(DEV, "XLA Ops", "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)",
           15, 30),                                     # overlaps the first
        ev(DEV, "XLA Ops", "%linear_scan_dot_pallas.1 = (f32[32,256]{1,0}, "
           "s8[32,256]{1,0}) custom-call(f32[1,1]{1,0} %t)", 120, 140),
        ev(DEV, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)",
           300, 310),                                   # after the window
        ev(DEV, "XLA Modules", "jit_gather(123)", 5, 35),   # not an op line
        ev(DEV, "XLA Modules", "jit_linear_search(456)", 115, 145),
    ]


def test_hand_made_window_busy_and_idle():
    red = tl.reduce(hand_made())
    assert red.window_s == pytest.approx(200e-9)
    assert red.busy_s == pytest.approx(40e-9)            # [10,30] + [120,140]
    lin = harness.load_module(harness.BENCH / "metrics"
                              / "linear_scan_roofline.py").KERNELS
    assert red.kernel_seconds(lin) == pytest.approx(20e-9)
    assert red.kernel_seconds([r"nothing"]) == 0.0
    assert red.top_ops(2) == [
        ["jit_linear_search/linear_scan_dot_pallas.1", pytest.approx(20e-9)],
        ["jit_gather/fusion.2", pytest.approx(15e-9)]]
    # gaps [0,10] in query, [30,120] and [140,200] in extract
    idle = dict((k, v) for k, v in red.idle_by_label())
    assert idle == {"extract": pytest.approx(150e-9),
                    "query": pytest.approx(10e-9)}


def test_no_device_op_reads_nothing():
    host_only = [e for e in hand_made() if e.plane == HOST]
    assert tl.reduce(host_only) is None


def test_events_round_trip(tmp_path):
    p = str(tmp_path / "ev.json.gz")
    tl.save_events(hand_made(), p)
    assert tl.load_events(p) == hand_made()


def test_recorded_chip_trace():
    """A slice of a traced window of the mixed cell on one v5e chip."""
    red = tl.reduce(tl.load_events(str(RECORDED)))
    assert 0 < red.busy_s < red.window_s
    metrics = harness.BENCH / "metrics"
    for name in ("linear_scan_roofline", "lsh_scan_roofline"):
        kernels = harness.load_module(metrics / f"{name}.py").KERNELS
        assert 0 < red.kernel_seconds(kernels) < red.busy_s
    labels = {k for k, _ in red.idle_by_label()}
    assert labels <= {"query", "extract", "request", "between requests"}
    assert sum(v for _, v in red.idle_by_label(100)) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)


# What the reduction and the device-trace metrics read on the two
# recorded slices before the reduction kept the program's spans
# (``work`` below stands for a run's work counts).  The program's spans
# must change none of it, to the bit.
WORK = {"dim": 128.0, "live_rows": 990000.0, "linear_calls": 2.0,
        "linear_rows": 32.0, "linear_pairs": 16e6, "lsh_rows": 32.0,
        "lsh_candidates": 250000.0}
PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
SLICES = {
    "trace_mixed_small.json.gz": {
        "busy_s": 0.641987286, "window_s": 1.649334298,
        "device_idle_share.index": 61.075975514576974,
        "linear_scan_roofline": 30.07664101525781,
        "lsh_scan_roofline": 0.7999261989799319,
        "top_ops": [
            ["jit_gather/fusion", 0.579477428],
            ["jit_lsh_search/lsh_scan_pallas.1", 0.019692962],
            ["jit_dynamic_slice/copy-done", 0.006317698],
            ["jit_gather/copy.11", 0.005742049],
            ["jit_squeeze/reduce", 0.005399725],
            ["jit_linear_search/linear_scan_dot_pallas.1", 0.004560364],
            ["jit_gather/while.1", 0.003957202],
            ["jit_lsh_search/fusion.2", 0.0037606170000000004],
            ["jit_gather/dynamic-update-slice.2", 0.003298033],
            ["jit_linear_search/multiply_reduce_fusion", 0.001473726],
        ],
        "idle_by_label": [
            ["extract", 0.7746949310000001],
            ["query", 0.232652081],
        ],
    },
    "trace_mixed_spans.json.gz": {
        "busy_s": 0.633652105, "window_s": 1.6213556420000002,
        "device_idle_share.index": 60.91837666051061,
        "linear_scan_roofline": 30.266058823841142,
        "lsh_scan_roofline": 1.2015566728332288,
        "top_ops": [
            ["jit_gather/fusion", 0.5778723370000001],
            ["jit_lsh_search/lsh_scan_pallas.1", 0.013110423000000001],
            ["jit_dynamic_slice/copy-done", 0.006845979],
            ["jit_squeeze/reduce", 0.005948419000000001],
            ["jit_gather/copy.11", 0.005854654],
            ["jit_linear_search/linear_scan_dot_pallas.1", 0.004531355],
            ["jit_gather/while.1", 0.003956449],
            ["jit_gather/dynamic-update-slice.2", 0.003298038],
            ["jit_lsh_search/fusion.2", 0.002507505],
            ["jit_linear_search/multiply_reduce_fusion", 0.001473551],
        ],
        "idle_by_label": [
            ["extract", 0.7974556730000001],
            ["query", 0.18957252000000002],
            ["request", 0.000675344],
        ],
    },
}


@pytest.mark.parametrize("name", sorted(SLICES))
def test_recorded_slice_reads_as_before(name):
    red = tl.reduce(tl.load_events(str(DATA / name)))
    want = SLICES[name]
    assert (red.busy_s, red.window_s) == (want["busy_s"], want["window_s"])
    assert red.top_ops(10) == want["top_ops"]
    assert red.idle_by_label(10) == want["idle_by_label"]
    ctx = types.SimpleNamespace(trace=red, work=WORK, peaks=PEAKS,
                                log=lambda *_: None)
    for metric in ("device_idle_share.index", "linear_scan_roofline",
                   "lsh_scan_roofline"):
        m = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
        assert m.read(ctx) == want[metric], metric
