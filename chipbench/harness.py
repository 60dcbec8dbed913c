"""Runs one cell of ``BENCHMARK.json`` and builds its result line.

Everything that belongs to one configuration, traffic mix or metric is
found by name:

* ``configs/<config>.json``: sizes, source, guarantees, and the
  ``system`` adapter that builds and drives the program
  (``systems/<system>.py``: ``System``, and ``inputs(seed, cfg, plan)``,
  the requests, the radius and the rows the control answers as the
  linear route); ``configs/<config>.reference.py`` is the plain
  reference that decides ``correct``; ``configs/<config>.small.json``
  holds the ``config`` and ``traffic`` overrides the CPU tests run the
  configuration's cells at (runs never read it);
* ``traffic/<traffic>.json``: a mix for ``generator.py``, driven by the
  loop it names (``loops/<loop>.py``, whose ``KEYS`` are the mix's keys
  of its own);
* ``metrics/<metric>.py``: ``read(ctx)`` returns the metric's value, or
  ``None`` where the run holds nothing to read.  ``ctx.trace`` is the
  traced run's ``trace.Reduced``: device numbers and the program's spans.

A run: set-up (data, system, warm-up of the shapes the pool produces),
then the window, then the device memory peak, the work counts (traced
runs), the system freed, and the reference over a sample of the
window's requests.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
import types
from contextlib import contextmanager
from pathlib import Path
from typing import Dict, Optional

import numpy as np

from chipbench import generator
from chipbench import trace as trace_lib

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path) -> types.ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    rel = path.relative_to(BENCH) if path.is_relative_to(BENCH) else path
    tag = "chipbench_" + "".join(c if c.isalnum() else "_"
                                 for c in str(rel))
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of the spec, with its configuration and traffic."""

    def __init__(self, name: str, spec: Optional[Dict] = None):
        spec = spec if spec is not None else _json(REPO / "BENCHMARK.json")
        self.spec = spec
        wl = [w for w in spec["workloads"] if w["name"] == name]
        if len(wl) != 1:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.workload = wl[0]
        entry = [c for c in spec["configs"]
                 if c["name"] == self.workload["config"]][0]
        self.config = _json(REPO / entry["file"])
        self.traffic = _json(BENCH / "traffic"
                             / f"{self.workload['traffic']}.json")
        self.reference_path = (BENCH / "configs"
                               / f"{self.workload['config']}.reference.py")
        self.system_path = BENCH / "systems" / f"{self.config['system']}.py"

    def metrics(self, traced: bool):
        """(name, unit) of the metrics this cell reports in such a run."""
        out = []
        for m in self.spec["per_layer" if traced else "end_to_end"]:
            cells = m.get("workloads")
            if cells is None or self.workload["name"] in cells:
                out.append((m["name"], m["unit"]))
        return out


class Recorder:
    """Host spans around the calls into each layer, on the host clock and
    in the profiler's trace (``chipbench.<name>``)."""

    def __init__(self):
        import jax

        self._annotate = jax.profiler.TraceAnnotation
        self.spans: Dict[str, list] = {}

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with self._annotate(trace_lib.SPAN + name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)


class Sampler:
    """A uniform sample of ``k`` of the window's requests, drawn from the
    seed (reservoir): their answers are kept for the reference."""

    def __init__(self, k: int, seed: int):
        self.k = int(k)
        self.rng = np.random.default_rng([int(seed), 5])
        self.kept: Dict[int, tuple] = {}
        self.seen = 0

    def offer(self, i: int, ids, counts) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept[i] = (ids, counts)
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            del self.kept[sorted(self.kept)[j]]
            self.kept[i] = (ids, counts)


class CompileCounter:
    """Counts the programs compiled or loaded from the compilation cache
    (JAX's monitoring events) until ``close``."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        self.n += event.endswith("backend_compile_duration")

    def close(self) -> int:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)
        return self.n


def device_info(chips: int) -> Dict:
    import jax

    devs = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def peaks_for(kind: str) -> Dict:
    """Published peaks of ``kind``; a kind without them is an error."""
    kinds = _json(BENCH / "peaks.json")["kinds"]
    if kind not in kinds:
        raise ValueError(f"no published peaks for device kind {kind!r}")
    return kinds[kind]


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             t_start: Optional[float] = None, spec: Optional[Dict] = None,
             config: Optional[Dict] = None, traffic: Optional[Dict] = None
             ) -> Dict:
    """One run of one cell; returns the result line as a dict.

    ``config`` and ``traffic`` replace the cell's own (tests run a cell
    at a small size this way).
    """
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(workload, spec)
    cfg = config if config is not None else cell.config
    plan = generator.plan(traffic if traffic is not None else cell.traffic,
                          seed)
    system_mod = load_module(cell.system_path)
    ref_mod = load_module(cell.reference_path)
    loop = load_module(generator.LOOPS / f"{plan.loop}.py")

    log(f"imports and spec: {time.perf_counter() - t_start:.3f} s")
    system = system_mod.System(cfg, seed, plan, log=log)
    t_warm = time.perf_counter()
    n_warm = system.warmup(Recorder())
    log(f"  set-up warm-up: {time.perf_counter() - t_warm:.3f} s")
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; radius {system.r:.6g}; warmed "
        f"{n_warm} request shapes")

    rec, sampler = Recorder(), Sampler(plan.check_requests, seed)
    compiles = CompileCounter()
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    if traced:
        jax.profiler.start_trace(tdir,
                                 profiler_options=trace_lib.profile_options())
    try:
        t0, served = loop.run(system, plan, seconds, rec, sampler)
    finally:
        if traced:
            jax.profiler.stop_trace()
        n_compiled = compiles.close()
    window_s = served[-1][2] - t0
    log(f"window {window_s:.3f} s: {len(served)} requests")
    if n_compiled:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
        raise RuntimeError(f"{n_compiled} programs compiled or loaded inside "
                           "the window: the warm-up missed a shape")
    device = device_info(int(cell.workload["chips"]))
    work = system.work_counts([(i, c) for i, _, _, c in served]) \
        if traced else {}
    sample = [(system.requests[i % len(system.requests)], ids,
               counts["linear"]) for i, (ids, counts)
              in sorted(sampler.kept.items())]
    r = system.r
    system.close()
    del system
    gc.collect()

    t_ref = time.perf_counter()
    ref = ref_mod.Reference(cfg, r)
    numbers, tally = ref_mod.check(ref, sample)
    del ref
    log(f"reference over {len(sample)} requests: "
        f"{time.perf_counter() - t_ref:.3f} s")
    correct = all(numbers[k] <= lim for k, lim in ref_mod.LIMITS.items())

    reduced, on_device = None, False
    if traced:
        try:
            reduced = trace_lib.Reduced(trace_lib.load_profile(tdir))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        # a trace with no device op (no chip) has no device numbers
        on_device = bool(reduced.ops)
        if on_device:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s

    ctx = types.SimpleNamespace(
        setup_s=setup_s, window_s=window_s, served=served, spans=rec.spans,
        work=work, trace=reduced, log=log,
        peaks=peaks_for(device["kind"]) if on_device else None)
    metrics = {}
    for name, unit in cell.metrics(traced):
        value = load_module(BENCH / "metrics" / f"{name}.py").read(ctx)
        if value is not None and math.isfinite(value):
            metrics[name] = {"value": float(value), "unit": unit}
    out = {"correct": bool(correct), "attempted": len(served), "failed": 0,
           "metrics": metrics, "device": device}
    if on_device:
        out["breakdown"] = {"device_ops": reduced.top_ops(10),
                            "idle_gaps": reduced.idle_by_label(10)}
    log("reference tallies: " + json.dumps(tally, sort_keys=True))
    out["checks"] = {k: {"value": numbers[k], "limit": ref_mod.LIMITS[k]}
                     for k in ref_mod.LIMITS}
    return out
