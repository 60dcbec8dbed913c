"""The program's own spans in a traced run, as ``trace.Reduced`` keeps
them: idle gaps labelled by the innermost program span under the harness
span, the per-layer metrics that read the spans' times and counts, and
``chipbench/program_spans.py``'s report."""
import json
import types
from pathlib import Path

import pytest

from chipbench import harness
from chipbench import program_spans as ps
from chipbench import trace as tl

DEV, HOST = "/device:TPU:0", "/host:CPU"
RECORDED = Path(__file__).parent / "data" / "trace_mixed_spans.json.gz"


READINGS = ["route_sync_ms.index", "search_dispatch_ms.index",
            "extract_wait_ms.index", "d2h_reads_per_request.index",
            "extract_useful_share.index"]


def ev(plane, name, start, end, **args):
    line = "XLA Ops" if plane == DEV else "python"
    return tl.Event(plane, line, name, float(start), float(end - start), args)


def harness_spans():
    return [
        ev(HOST, "chipbench.request", 0, 100),
        ev(HOST, "chipbench.query", 0, 40),
        ev(HOST, "chipbench.extract", 40, 100),
        ev(HOST, "chipbench.request", 110, 200),
        ev(HOST, "chipbench.query", 110, 150),
        ev(HOST, "chipbench.extract", 150, 200),
        ev(DEV, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 20),
        ev(DEV, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 20, 45),
        ev(DEV, "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 120, 140),
    ]


def program_spans():
    return [
        ev(HOST, "repro.index.query", 1, 39, batch=1, rows=2),
        ev(HOST, "repro.index.delta_count.sync", 2, 4, batch=1, reads=1),
        ev(HOST, "repro.engine.route.sync", 4, 12, batch=1, reads=1,
           lsh_rows=1, linear_rows=1),
        ev(HOST, "repro.engine.search", 12, 38, batch=1, route="lsh"),
        ev(HOST, "repro.result.reported", 40, 70, batch=1, route="lsh",
           reported=100, bytes=900),
        ev(HOST, "repro.result.copy.sync", 41, 69, batch=1, reads=3),
        ev(HOST, "repro.result.reported", 70, 100, batch=1, route="linear",
           reported=0, bytes=900),
        ev(HOST, "repro.result.copy.sync", 71, 99, batch=1, reads=3),
        ev(HOST, "repro.index.query", 110, 150, batch=2, rows=1),
        ev(HOST, "repro.index.delta_count.sync", 111, 112, batch=2, reads=1),
        ev(HOST, "repro.engine.route.sync", 112, 118, batch=2, reads=1,
           lsh_rows=1, linear_rows=0),
        ev(HOST, "repro.engine.search", 120, 148, batch=2, route="lsh"),
        ev(HOST, "repro.result.reported", 150, 200, batch=2, route="lsh",
           reported=50, bytes=450),
        ev(HOST, "repro.result.copy.sync", 152, 198, batch=2, reads=3),
        ev(HOST, "repro.engine.search", 300, 310, route="lsh"),  # after
    ]


def read(name, red):
    """The per-layer metric ``name`` of a run whose trace reduces to
    ``red``."""
    metric = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
    return metric.read(types.SimpleNamespace(trace=red))


def test_idle_gaps_named_by_program_span():
    red = tl.Reduced(harness_spans() + program_spans())
    assert red.n_requests == 2 and len(red.program) == 14
    # gaps [0,10] in route.sync; [45,120] (midpoint 82.5) and [140,200]
    # in the second and third row copies
    idle = dict((k, v) for k, v in red.idle_by_label(program=True))
    assert idle == {"query/engine.route.sync": pytest.approx(10e-9),
                    "extract/result.copy.sync": pytest.approx(135e-9)}
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)


def test_program_spans_change_no_other_reduction():
    plain = tl.reduce(harness_spans())
    spans = tl.reduce(harness_spans() + program_spans())
    assert (spans.window_s, spans.busy_s) == (plain.window_s, plain.busy_s)
    assert spans.top_ops() == plain.top_ops()
    # the harness's labels do not see the program's spans
    assert spans.idle_by_label() == plain.idle_by_label()
    assert {k.split("/")[0] for k, _ in spans.idle_by_label(program=True)} \
        == {k for k, _ in plain.idle_by_label()}
    assert plain.idle_by_label(program=True) == plain.idle_by_label()
    assert plain.program == []


@pytest.mark.parametrize("name,value", [
    ("route_sync_ms.index", (8 + 6) / 2 * 1e-6),
    ("search_dispatch_ms.index", (26 + 28) / 2 * 1e-6),
    ("extract_wait_ms.index", (28 + 28 + 46) / 2 * 1e-6),
    ("d2h_reads_per_request.index", (1 + 1 + 3 + 3 + 1 + 1 + 3) / 2),
    ("extract_useful_share.index", 100.0 * 8 * 150 / (900 + 900 + 450)),
])
def test_reading_of_program_spans(name, value):
    spans = tl.Reduced(harness_spans() + program_spans())
    assert read(name, spans) == pytest.approx(value)
    # a program without the spans, or a run without a trace, reads nothing
    assert read(name, tl.Reduced(harness_spans())) is None
    assert read(name, None) is None


def test_report_sets_spans_beside_harness_metrics():
    red = tl.Reduced(harness_spans() + program_spans())
    got = ps.report(red, {"query_call_ms.index": {"value": 39e-6},
                          "extract_ms.index": {"value": 55e-6}})
    assert got["idle_gaps"] == red.idle_by_label(1000, program=True)
    assert got["spans"]["repro.result.copy.sync"]["per_request"] == 1.5
    assert got["idle_named_share"] == 1.0
    # (38 + 40) / 2 ns against 39, (60 + 50) / 2 against 55
    assert got["repro.index.query / query_call_ms.index"] == \
        pytest.approx(1.0)
    assert got["repro.result.reported / extract_ms.index"] == \
        pytest.approx(1.0)


def test_load_profile_keeps_program_args(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=tl.profile_options())
    with jax.profiler.TraceAnnotation("chipbench.request"):
        with jax.profiler.TraceAnnotation("repro.result.copy.sync", reads=3):
            pass
    jax.profiler.stop_trace()
    got = {e.name: e.args for e in tl.load_profile(str(tmp_path))
           if e.name.startswith(("chipbench.", "repro."))}
    assert got == {"chipbench.request": {},
                   "repro.result.copy.sync": {"reads": 3}}


def test_saved_slice_round_trips(tmp_path):
    events = harness_spans() + program_spans()
    kept = ps.slice_events(events, 1, 1)
    assert {e.name for e in kept if e.name.startswith("chipbench.")} == \
        {"chipbench.request", "chipbench.query", "chipbench.extract"}
    assert all(e.start_ns >= 110 for e in kept if e.plane == HOST)
    tl.save_events(kept, str(tmp_path / "s.json.gz"))
    assert tl.load_events(str(tmp_path / "s.json.gz")) == kept


def test_recorded_chip_trace_with_program_spans():
    """A slice of a traced window of the mixed cell on one v5e chip, with
    the program's spans: idle time in query and extraction is named by
    program span, and still sums to the window less the busy time."""
    red = tl.reduce(tl.load_events(str(RECORDED)))
    assert red.program and 0 < red.busy_s < red.window_s
    idle = red.idle_by_label(100, program=True)
    assert sum(v for _, v in idle) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    in_layers = [(k, v) for k, v in idle
                 if k.split("/")[0] in ("query", "extract")]
    named = sum(v for k, v in in_layers if "/" in k)
    assert named >= 0.9 * sum(v for _, v in in_layers)
    assert {k.split("/")[0] for k, _ in in_layers} == {"query", "extract"}
    assert any(k.startswith("query/") for k, _ in in_layers)
    assert any(k.startswith("extract/") for k, _ in in_layers)
    for name in READINGS:
        assert read(name, red) > 0
    # 32 rows a request: the delta count, the route sync, 3 copies a row
    assert read("d2h_reads_per_request.index", red) == 2 + 3 * 32


# what ``program_spans.py`` read on the recorded slice when it reduced
# the program's spans itself (``ProgramReduced``, ``READINGS``)
RECORDED_READINGS = {
    "route_sync_ms.index": 0.627385,
    "search_dispatch_ms.index": 326.502014,
    "extract_wait_ms.index": 405.0269025,
    "d2h_reads_per_request.index": 98.0,
    "extract_useful_share.index": 40.00527280409772,
}
RECORDED_PROGRAM_IDLE = [
    ["extract/result.copy.sync", 0.7969131450000001],
    ["query/engine.estimate", 0.14340270800000002],
    ["query/engine.segment", 0.023439125],
    ["query/engine.search", 0.019952034],
    ["query/engine.route.sync", 0.0008230700000000001],
    ["query/index.query", 0.0007350270000000001],
    ["request", 0.000675344],
    ["query/index.delta_count.sync", 0.000633575],
    ["query/index.hash", 0.000586981],
    ["extract/result.reported", 0.000542526],
]


@pytest.mark.parametrize("name", sorted(RECORDED_READINGS))
def test_recorded_reading_as_program_spans_read_it(name):
    red = tl.reduce(tl.load_events(str(RECORDED)))
    assert read(name, red) == RECORDED_READINGS[name]


def test_recorded_program_idle_labels_as_program_spans_read_them():
    red = tl.reduce(tl.load_events(str(RECORDED)))
    assert red.idle_by_label(10, program=True) == RECORDED_PROGRAM_IDLE


def test_readings_are_metrics_of_the_spec():
    """Each reading is a per-layer metric of cells of the spec, and what
    it reads is the program's: neither harness span nor device."""
    spec = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    for name in READINGS:
        assert set(per_layer[name]["workloads"]) <= cells
        assert per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "queries_per_s"
        assert per_layer[name]["source"] in ("program_span",
                                             "program_counter")
