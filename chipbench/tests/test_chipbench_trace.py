"""Trace reduction: busy and idle share, kernel time by stored name, and
idle gaps labelled by harness span."""
from pathlib import Path

import pytest

from chipbench import harness
from chipbench import trace as tl

DEV, HOST = "/device:TPU:0", "/host:CPU"
RECORDED = Path(__file__).parent / "data" / "trace_mixed_small.json.gz"


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
