"""The program's own spans (``repro.*``) in a traced run of a cell.

The program opens ``jax.profiler.TraceAnnotation`` spans on its query
and extraction path, with host-only counts as arguments
(docs/observability.md).  ``trace.py`` reduces the harness's spans and
the device planes; this module reads the program's spans from the same
profile, arguments included:

* ``load_profile`` and ``load_events`` are ``trace``'s, with the
  arguments of the ``repro.*`` host spans kept (``Span.args``).
* ``ProgramReduced`` is ``trace.Reduced`` with the program's spans inside
  the window.  Its idle gaps carry ``trace``'s label (the innermost
  harness span) and, where a program span covers the gap's midpoint too,
  ``/`` and the innermost of those (``extract/result.copy.sync``).
* ``READINGS``: five numbers of the spans, each over the window's
  requests.

As a script it runs one cell traced, as ``run.py --trace 1`` does,
prints the harness's result line and then one line of the program's
readings; ``--slice`` also saves the events of two requests, for the
tests (``chipbench/tests/data/``)::

    python3 chipbench/program_spans.py --workload hybridlsh-densecore-l2.mixed \\
        --seed 7 --seconds 25 \\
        --slice chipbench/tests/data/trace_mixed_spans.json.gz
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

T_START = time.perf_counter()   # set-up is timed from process start

if __name__ == "__main__":
    REPO = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import trace as trace_lib  # noqa: E402

PROGRAM = "repro."
SYNC = ".sync"
QUERY = PROGRAM + "index.query"
ROUTE_SYNC = PROGRAM + "engine.route.sync"
SEARCH = PROGRAM + "engine.search"
REPORTED = PROGRAM + "result.reported"
COPY_SYNC = PROGRAM + "result.copy.sync"
PAIR_BYTES = 8   # an answer is an int32 id and a float32 distance


@dataclasses.dataclass(frozen=True)
class Span(trace_lib.Event):
    """An event with the arguments the program gave its span."""
    args: Dict = dataclasses.field(default_factory=dict, hash=False)


def load_profile(trace_dir: str) -> List[Span]:
    """Every event of the one ``.xplane.pb`` the profiler wrote, with the
    arguments of the program's host spans."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        host = not plane.name.startswith(trace_lib.DEVICE_PLANE)
        for line in plane.lines:
            for e in line.events:
                args = (dict(e.stats) if host and e.name.startswith(PROGRAM)
                        else {})
                out.append(Span(plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns),
                                args))
    return out


def load_events(path: str) -> List[Span]:
    """Events saved by ``trace.save_events``, with or without arguments."""
    with gzip.open(path, "rt") as f:
        return [Span(*row) for row in json.load(f)]


class _Innermost:
    """The innermost of a set of host spans that covers a time.  Spans of
    one name never overlap (one caller), so a bisect per name finds the
    one covering t; the shortest cover is innermost."""

    def __init__(self, spans: Sequence[trace_lib.Event]):
        self.by_name: Dict[str, List[trace_lib.Event]] = {}
        for e in sorted(spans, key=lambda e: e.start_ns):
            self.by_name.setdefault(e.name, []).append(e)
        self.starts = {n: [e.start_ns for e in v]
                       for n, v in self.by_name.items()}

    def at(self, t: float) -> Optional[trace_lib.Event]:
        cover = []
        for n, v in self.by_name.items():
            i = bisect.bisect_right(self.starts[n], t) - 1
            if i >= 0 and v[i].end_ns >= t:
                cover.append(v[i])
        return min(cover, key=lambda e: e.dur_ns) if cover else None


class ProgramReduced(trace_lib.Reduced):
    """``trace.Reduced`` with the program's host spans inside the window."""

    def __init__(self, events: Sequence[trace_lib.Event]):
        super().__init__(events)
        self.n_requests = sum(e.name == trace_lib.REQUEST_SPAN
                              for e in events)
        self.program = [e for e in events if e.name.startswith(PROGRAM)
                        and not e.plane.startswith(trace_lib.DEVICE_PLANE)
                        and e.end_ns > self.lo and e.start_ns < self.hi]

    def ms_per_request(self, name: str) -> Optional[float]:
        """Summed host time of the ``name`` spans over the window's
        requests, in ms; ``None`` where it has none."""
        durs = [e.dur_ns for e in self.program if e.name == name]
        return sum(durs) * 1e-6 / self.n_requests if durs else None

    def idle_by_label(self, k: int = 10) -> List[List]:
        harness = _Innermost(self.spans)
        program = _Innermost(self.program)

        def label(t: float) -> str:
            cover = harness.at(t)
            lab = ("between requests" if cover is None
                   else cover.name[len(trace_lib.SPAN):])
            inner = program.at(t)
            if inner is not None:
                lab += "/" + inner.name[len(PROGRAM):]
            return lab

        tot: Dict[str, float] = {}
        for s, e in self.gaps:
            lab = label((s + e) / 2)
            tot[lab] = tot.get(lab, 0.0) + (e - s)
        n_planes = max(len(self.busy_by_plane), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9 / n_planes] for n, v in top]


def d2h_reads_per_request(red: ProgramReduced) -> Optional[float]:
    """The ``reads`` of the ``*.sync`` spans, over the requests."""
    reads = [e.args.get("reads", 0) for e in red.program
             if e.name.endswith(SYNC)]
    return sum(reads) / red.n_requests if reads else None


def extract_useful_share(red: ProgramReduced) -> Optional[float]:
    """Answer bytes (8 B a reported id) over the ``bytes`` the
    ``repro.result.reported`` spans copied, in %."""
    rows = [e.args for e in red.program if e.name == REPORTED]
    copied = sum(a.get("bytes", 0) for a in rows)
    if not copied:
        return None
    return 100.0 * PAIR_BYTES * sum(a.get("reported", 0)
                                    for a in rows) / copied


# name, unit and reading of each number; the names are those the
# benchmark would give them as per-layer metrics
READINGS = {
    "route_sync_ms.index": ("ms", lambda red: red.ms_per_request(ROUTE_SYNC)),
    "search_dispatch_ms.index": ("ms",
                                 lambda red: red.ms_per_request(SEARCH)),
    "extract_wait_ms.index": ("ms",
                              lambda red: red.ms_per_request(COPY_SYNC)),
    "d2h_reads_per_request.index": ("reads", d2h_reads_per_request),
    "extract_useful_share.index": ("%", extract_useful_share),
}


def report(red: ProgramReduced, harness_metrics: Dict) -> Dict:
    """The readings, each program span's count and ms a request, the idle
    gaps by program span, and the program's spans set beside the
    harness's own (``query_call_ms.index``, ``extract_ms.index``)."""
    out: Dict = {"readings": {}}
    for name, (unit, read) in READINGS.items():
        value = read(red)
        if value is not None:
            out["readings"][name] = {"value": value, "unit": unit}
    names = sorted({e.name for e in red.program})
    out["spans"] = {n: {"per_request": sum(e.name == n for e in red.program)
                        / red.n_requests,
                        "ms": red.ms_per_request(n)} for n in names}
    idle = red.idle_by_label(1000)
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
    host = (trace_lib.SPAN, PROGRAM)
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
    kept = {}

    def keep(trace_dir):
        kept["events"] = load_profile(trace_dir)
        return kept["events"]

    # the harness reads the profile through trace.load_profile; the spans
    # it hands on are Events, so its own reductions read as in run.py
    trace_lib.load_profile = keep
    out = harness.run_cell(args.workload, args.seed, args.seconds, True,
                           t_start=T_START)
    print(json.dumps(out), flush=True)
    got = report(ProgramReduced(kept["events"]), out["metrics"])
    if args.slice:
        events = slice_events(kept["events"], 1, 2)
        trace_lib.save_events(events, args.slice)
        got["slice"] = {"events": len(events),
                        "bytes": os.path.getsize(args.slice)}
    print(json.dumps(got), flush=True)


if __name__ == "__main__":
    main()
