"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read once into a flat list of ``Event`` (plane, line, name,
start, duration in ns) and everything else is computed from that list,
so the reduction can be checked on a small recorded trace
(``chipbench/tests/data/``).

* The window is the span from the first ``chipbench.request`` host span
  to the end of the last one (the harness wraps every request in it).
* Device work is the op line (``XLA Ops``) of each device plane.  Busy
  time is the union of its intervals inside the window, averaged over
  the devices that ran an op; the idle share is 1 - busy / window.
* An op's event name is its HLO text; its short name is the part before
  `` = `` (``%lsh_scan_pallas.1``).  Kernel time is the summed duration
  of the ops whose short name matches one of a kernel's stored names.
* The breakdown names an op by the jitted program that ran it (the
  ``XLA Modules`` line, hash dropped) and its short name.
* Each idle gap of the device is labelled by the innermost harness span
  (``chipbench.<name>``) that covers its midpoint, ``between requests``
  when none does, and the idle time is summed per label.
* The program's own host spans (``repro.<name>``, docs/observability.md)
  inside the window are kept with the arguments the program gave them
  (``Event.args``: host-only counts), for the per-layer metrics that read
  them (``ms_per_request``, ``program``).  They never enter the busy
  time or the harness's idle labels; ``idle_by_label(program=True)``
  appends to a gap's label ``/`` and the innermost program span covering
  its midpoint (``extract/result.copy.sync``).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = "/device:"
SPAN = "chipbench."
REQUEST_SPAN = SPAN + "request"
PROGRAM = "repro."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    args: Dict = dataclasses.field(default_factory=dict, hash=False)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def short(self) -> str:
        return self.name.split(" = ", 1)[0]


def profile_options():
    """Device and host tracing, without the Python function tracer (it
    slows the host and makes most of the trace)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def load_profile(trace_dir: str) -> List[Event]:
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
        host = not plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            for e in line.events:
                args = (dict(e.stats) if host and e.name.startswith(PROGRAM)
                        else {})
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 args))
    return out


def save_events(events: Iterable[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: str) -> List[Event]:
    """Events saved by ``save_events``, with or without arguments."""
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


def _union(iv: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


class _Innermost:
    """The innermost of a set of host spans that covers a time.  Spans of
    one name never overlap (one caller), so a bisect per name finds the
    one covering t; the shortest cover is innermost."""

    def __init__(self, spans: Sequence[Event]):
        self.by_name: Dict[str, List[Event]] = {}
        for e in sorted(spans, key=lambda e: e.start_ns):
            self.by_name.setdefault(e.name, []).append(e)
        self.starts = {n: [e.start_ns for e in v]
                       for n, v in self.by_name.items()}

    def at(self, t: float) -> Optional[Event]:
        cover = []
        for n, v in self.by_name.items():
            i = bisect.bisect_right(self.starts[n], t) - 1
            if i >= 0 and v[i].end_ns >= t:
                cover.append(v[i])
        return min(cover, key=lambda e: e.dur_ns) if cover else None


class Reduced:
    """The device numbers and the program's spans of one traced window."""

    def __init__(self, events: Sequence[Event]):
        req = [e for e in events if e.name == REQUEST_SPAN]
        if not req:
            raise ValueError("no harness request span in the trace")
        self.n_requests = len(req)
        self.lo = min(e.start_ns for e in req)
        self.hi = max(e.end_ns for e in req)
        self.ops = [e for e in events
                    if e.plane.startswith(DEVICE_PLANE) and e.line == OPS_LINE
                    and e.end_ns > self.lo and e.start_ns < self.hi]
        self.spans = [e for e in events if e.name.startswith(SPAN)
                      and not e.plane.startswith(DEVICE_PLANE)]
        self.program = [e for e in events if e.name.startswith(PROGRAM)
                        and not e.plane.startswith(DEVICE_PLANE)
                        and e.end_ns > self.lo and e.start_ns < self.hi]
        self.modules = sorted((e for e in events
                               if e.plane.startswith(DEVICE_PLANE)
                               and e.line == MODULES_LINE),
                              key=lambda e: (e.plane, e.start_ns))
        self._module_keys = [(m.plane, m.start_ns) for m in self.modules]
        planes = sorted({e.plane for e in self.ops})
        self.busy_by_plane = {}
        self.gaps: List[Tuple[float, float]] = []
        for p in planes:
            u = _clip(_union([(e.start_ns, e.end_ns) for e in self.ops
                              if e.plane == p]), self.lo, self.hi)
            self.busy_by_plane[p] = sum(e - s for s, e in u)
            edges = [self.lo] + [t for iv in u for t in iv] + [self.hi]
            self.gaps += [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        if not self.busy_by_plane:
            return 0.0
        return (sum(self.busy_by_plane.values()) / len(self.busy_by_plane)
                * 1e-9)

    def kernel_seconds(self, names: Sequence[str]) -> float:
        """Summed device time of the ops whose short name matches one of
        the patterns."""
        pats = [re.compile(n) for n in names]
        return sum(e.dur_ns for e in self.ops
                   if any(p.fullmatch(e.short) for p in pats)) * 1e-9

    def _module(self, op: Event) -> str:
        i = bisect.bisect_right(self._module_keys,
                                (op.plane, op.start_ns)) - 1
        if i >= 0:
            m = self.modules[i]
            if m.plane == op.plane and m.end_ns >= op.start_ns:
                return m.name.split("(", 1)[0]
        return "?"

    def top_ops(self, k: int = 10) -> List[List]:
        """The ops that took most device time, as ``module/op``."""
        tot: Dict[str, float] = {}
        for e in self.ops:
            name = f"{self._module(e)}/{e.short.lstrip('%')}"
            tot[name] = tot.get(name, 0.0) + e.dur_ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9] for n, v in top]

    def ms_per_request(self, name: str) -> Optional[float]:
        """Summed host time of the program's ``name`` spans over the
        window's requests, in ms; ``None`` where it has none."""
        durs = [e.dur_ns for e in self.program if e.name == name]
        return sum(durs) * 1e-6 / self.n_requests if durs else None

    def idle_by_label(self, k: int = 10,
                      program: bool = False) -> List[List]:
        """Idle device time summed by the label of each gap, the largest
        ``k``; with ``program`` the labels name the program span too."""
        harness = _Innermost(self.spans)
        inner = _Innermost(self.program) if program else None

        def label(t: float) -> str:
            cover = harness.at(t)
            lab = ("between requests" if cover is None
                   else cover.name[len(SPAN):])
            if inner is not None:
                span = inner.at(t)
                if span is not None:
                    lab += "/" + span.name[len(PROGRAM):]
            return lab

        tot: Dict[str, float] = {}
        for s, e in self.gaps:
            lab = label((s + e) / 2)
            tot[lab] = tot.get(lab, 0.0) + (e - s)
        n_planes = max(len(self.busy_by_plane), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v * 1e-9 / n_planes] for n, v in top]


def reduce(events: Sequence[Event]) -> Optional[Reduced]:
    """``None`` when the trace holds no device op inside the window."""
    red = Reduced(events)
    return red if red.ops else None
