"""The program's own spans (``repro.*``) in a traced run of a cell.

The program opens ``jax.profiler.TraceAnnotation`` spans on its query
and extraction path, with host-only counts as arguments
(docs/observability.md).  ``trace.Reduced`` keeps them, and the
per-layer metrics that read them are on the result line
(``route_sync_ms.index``, ``search_dispatch_ms.index``,
``extract_wait_ms.index``, ``d2h_reads_per_request.index``,
``extract_useful_share.index``).  This script shows the rest of what
the spans say about the same run.

It runs one cell traced, as ``run.py --trace 1`` does, prints the
harness's result line and then one line of ``report``: each program
span's count and ms a request, the idle gaps named by program span
(``trace.Reduced.idle_by_label(program=True)``), and the program's spans
set beside the harness's own.  ``--slice`` also saves the events of two
requests, for the tests (``chipbench/tests/data/``)::

    python3 chipbench/program_spans.py --workload hybridlsh-densecore-l2.mixed \\
        --seed 7 --seconds 25 \\
        --slice chipbench/tests/data/trace_mixed_spans.json.gz
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path
from typing import Dict

T_START = time.perf_counter()   # set-up is timed from process start

if __name__ == "__main__":
    REPO = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import trace as trace_lib  # noqa: E402

QUERY = trace_lib.PROGRAM + "index.query"
REPORTED = trace_lib.PROGRAM + "result.reported"


def report(red: trace_lib.Reduced, harness_metrics: Dict) -> Dict:
    """Each program span's count and ms a request, the idle gaps by
    program span, and the program's spans set beside the harness's own
    (``query_call_ms.index``, ``extract_ms.index``)."""
    out: Dict = {}
    names = sorted({e.name for e in red.program})
    out["spans"] = {n: {"per_request": sum(e.name == n for e in red.program)
                        / red.n_requests,
                        "ms": red.ms_per_request(n)} for n in names}
    idle = red.idle_by_label(1000, program=True)
    out["idle_gaps"] = idle
    layer = [(k, v) for k, v in idle
             if k.split("/")[0] in ("query", "extract")]
    total = sum(v for _, v in layer)
    out["idle_named_share"] = (sum(v for k, v in layer if "/" in k) / total
                               if total else None)
    for span, metric in ((QUERY, "query_call_ms.index"),
                         (REPORTED, "extract_ms.index")):
        mine = red.ms_per_request(span)
        theirs = harness_metrics.get(metric, {}).get("value")
        if mine is not None and theirs:
            out[f"{span} / {metric}"] = mine / theirs
    return out


def slice_events(events, first: int, count: int):
    """The events of requests ``first`` .. ``first + count - 1``: host
    spans of the harness and the program inside them, and the device's op
    and module events that overlap them."""
    req = sorted((e for e in events if e.name == trace_lib.REQUEST_SPAN),
                 key=lambda e: e.start_ns)[first:first + count]
    lo, hi = req[0].start_ns, req[-1].end_ns
    host = (trace_lib.SPAN, trace_lib.PROGRAM)
    out = []
    for e in events:
        if e.plane.startswith(trace_lib.DEVICE_PLANE):
            if (e.line in (trace_lib.OPS_LINE, trace_lib.MODULES_LINE)
                    and e.end_ns > lo and e.start_ns < hi):
                out.append(e)
        elif e.name.startswith(host) and e.start_ns >= lo and e.end_ns <= hi:
            out.append(e)
    return out


def main() -> None:
    import argparse

    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slice", default=None,
                    help="save the events of the window's 2nd and 3rd "
                         "requests here")
    args = ap.parse_args()

    run = harness.load_module(harness.BENCH / "run.py")
    run.require_chips(int(harness.Cell(args.workload).workload["chips"]))
    run.enable_cache()
    load, kept = trace_lib.load_profile, {}

    def keep(trace_dir):
        kept["events"] = load(trace_dir)
        return kept["events"]

    # the harness deletes the profile once read: keep its events
    trace_lib.load_profile = keep
    out = harness.run_cell(args.workload, args.seed, args.seconds, True,
                           t_start=T_START)
    print(json.dumps(out), flush=True)
    got = report(trace_lib.Reduced(kept["events"]), out["metrics"])
    if args.slice:
        events = slice_events(kept["events"], 1, 2)
        trace_lib.save_events(events, args.slice)
        got["slice"] = {"events": len(events),
                        "bytes": os.path.getsize(args.slice)}
    print(json.dumps(got), flush=True)


if __name__ == "__main__":
    main()
