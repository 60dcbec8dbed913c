"""Observability substrate: registry, tracer, event log, schemas.

The load-bearing contracts: (1) tracing is observation only — a traced
query returns bit-identical results to the untraced fast path; (2) the
stats-key schemas in ``repro.obs.schema`` are asserted *exact*, so a
renamed key fails in review instead of breaking dashboards after
merge; (3) compaction work time is measured once — the driver and the
index report the same ``work_seconds`` dict.
"""
import json
import pathlib
import re
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CostModel
from repro.core.lsh import make_family
from repro.obs import (NULL_REGISTRY, SPAN_FIELDS, EventLog, MetricsRegistry,
                       Observability, QueryTracer, WorkPhases, time_block,
                       to_prometheus)
from repro.obs.schema import (DRIVER_STATS_KEYS, EVENT_BASE_FIELDS,
                              INDEX_STATS_KEYS, SHARDED_INDEX_EXTRA_KEYS,
                              WORK_PHASE_KEYS)
from repro.streaming import (CompactionDriver, CompactionPolicy,
                             DynamicHybridIndex)

D, L = 8, 4


def _dyn(obs=None, **kw):
    kw.setdefault("policy", CompactionPolicy(delta_fill=1.0,
                                             tombstone_ratio=2.0, fanout=2))
    kw.setdefault("delta_capacity", 128)
    return DynamicHybridIndex(make_family("l2", d=D, L=L, r=1.0),
                              num_buckets=256, m=32, cap=256, key=0,
                              cost_model=CostModel(alpha=1.0, beta=1.0),
                              obs=obs, **kw)


def _data(n=512, seed=0):
    rng = np.random.default_rng(seed)
    # half clustered (LSH-friendly), half spread — both routes exercised
    a = rng.normal(size=(n // 2, D)).astype(np.float32) * 0.05
    b = rng.normal(size=(n - n // 2, D)).astype(np.float32) * 3.0
    return np.concatenate([a, b])


# ---------------------------------------------------------------- registry
def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("c_total", help="a counter")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("g", help="a gauge")
    g.set(7.5)
    assert g.value == 7.5
    h = reg.histogram("h_seconds", buckets=(1.0, 10.0), help="a histogram")
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 3 and h.sum == 55.5
    # cumulative buckets: <=1 gets 1, <=10 gets 2, +Inf gets 3
    assert [n for _, n in h.cumulative()] == [1, 2, 3]


def test_registry_labels_key_identity():
    reg = MetricsRegistry(enabled=True)
    a = reg.counter("x_total", labels={"route": "lsh"})
    b = reg.counter("x_total", labels={"route": "lsh"})
    c = reg.counter("x_total", labels={"route": "linear"})
    assert a is b and a is not c
    a.inc(2)
    snap = reg.snapshot()
    assert json.dumps(snap)            # JSON-serializable
    assert snap["counters"]['x_total{route="lsh"}'] == 2
    assert snap["counters"]['x_total{route="linear"}'] == 0


def test_registry_disabled_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c_total")
    c.inc(10)
    assert c.value == 0                 # shared null instrument
    assert reg.collect() == []
    assert reg.snapshot()["counters"] == {}
    # the shared null registry behaves the same
    NULL_REGISTRY.counter("whatever").inc()
    assert NULL_REGISTRY.collect() == []


def test_registry_thread_safety_smoke():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("n_total")

    def work():
        for _ in range(1000):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 4000


def test_prometheus_exposition_format():
    reg = MetricsRegistry(enabled=True)
    reg.counter("q_total", help="queries", labels={"route": "lsh"}).inc(3)
    reg.gauge("live").set(12)
    h = reg.histogram("lat_seconds", buckets=(0.1,), help="latency")
    h.observe(0.05)
    h.observe(0.5)
    text = to_prometheus(reg)
    assert "# TYPE q_total counter" in text
    assert 'q_total{route="lsh"} 3' in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_sum" in text and "lat_seconds_count 2" in text
    # every non-comment line is "name{labels} value"
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert re.match(r'^[a-zA-Z_:][\w:]*(\{[^}]*\})? \S+$', line)


def test_work_phases_and_time_block():
    ph = WorkPhases("stage", "apply")
    with time_block(phases=ph, phase="stage") as tb:
        pass
    assert tb.elapsed >= 0
    ph.add("apply", 1.5)
    d = ph.as_dict()
    assert set(d) == {"stage", "apply", "total"}
    assert d["total"] == pytest.approx(d["stage"] + 1.5)
    assert ph.total == pytest.approx(d["total"])


# --------------------------------------------------------------- event log
def test_event_log_ring_bound_and_seq():
    log = EventLog(capacity=4)
    for i in range(10):
        log.emit("tick", i=i)
    assert len(log) == 4 and log.seq == 10 and log.dropped == 6
    evs = log.events()
    assert [e["i"] for e in evs] == [6, 7, 8, 9]        # newest-last
    assert all(EVENT_BASE_FIELDS <= set(e) for e in evs)
    log.emit("other")
    assert log.events(kind="other")[0]["seq"] == 10
    assert log.counts_by_kind() == {"tick": 3, "other": 1}
    assert len(log.events(limit=2)) == 2


def test_event_log_disabled_noop():
    log = EventLog(capacity=4, enabled=False)
    log.emit("tick")
    assert len(log) == 0 and log.seq == 0


# ------------------------------------------------------------ query tracing
def test_traced_query_results_identical_and_spans():
    obs = Observability.create(trace_capacity=1024, trace_sample_every=1)
    obs.tracer.enabled = False
    x = _data()
    idx = _dyn(obs=obs).build(x[:384])
    idx.insert(x[384:])                 # freeze + delta: multiple segments
    q = jnp.asarray(x[::40][:12])

    plain = idx.query(q, 1.2).neighbor_sets()
    obs.tracer.enabled = True
    traced = idx.query(q, 1.2).neighbor_sets()
    assert traced == plain              # tracing is observation only

    spans = obs.tracer.spans()
    assert len(spans) == 12
    assert all(set(SPAN_FIELDS) <= set(s) for s in spans)
    for s in spans:
        assert s["strategy"] in ("lsh", "linear") and not s["forced"]
        assert s["cand_actual"] <= idx.n
        # re-priced Eq. 1 must actually use cand_actual
        assert s["lsh_cost_actual"] == pytest.approx(
            s["collisions"] + s["cand_actual"])
    rate = obs.tracer.misroute_rate
    assert np.isfinite(rate) and 0.0 <= rate <= 1.0


def test_forced_queries_excluded_from_rate():
    obs = Observability.create(trace_sample_every=1)
    x = _data(256)
    idx = _dyn(obs=obs, delta_capacity=512).build(x)
    q = jnp.asarray(x[:8])
    idx.query(q, 1.2, force="lsh")
    idx.query(q, 1.2, force="linear")
    s = obs.tracer.summary()
    assert s["queries"] == 0 and s["forced_queries"] == 16
    assert len(obs.tracer.spans(strategy="lsh")) == 8
    assert all(sp["forced"] for sp in obs.tracer.spans())


def test_tracer_sampling_gates_batches():
    obs = Observability.create(trace_sample_every=4)
    x = _data(256)
    idx = _dyn(obs=obs, delta_capacity=512).build(x)
    q = jnp.asarray(x[:4])
    for _ in range(8):
        idx.query(q, 1.2)
    s = obs.tracer.summary()
    # batches 0 and 4 sample; 8 batches seen
    assert s["batches_seen"] == 8 and s["batches_traced"] == 2
    assert s["queries"] == 8


def test_queries_total_counts_every_batch():
    """The route counter sees every batch, not just the sampled ones;
    the misroute denominator stays the sampled rows."""
    obs = Observability.create(trace_sample_every=4)
    x = _data(256)
    idx = _dyn(obs=obs, delta_capacity=512).build(x)
    q = jnp.asarray(x[:4])
    for _ in range(8):
        idx.query(q, 1.2)
    counters = obs.registry.snapshot()["counters"]
    by_route = {k: v for k, v in counters.items()
                if k.startswith("repro_queries_total")}
    assert set(by_route) <= {'repro_queries_total{route="lsh"}',
                             'repro_queries_total{route="linear"}'}
    assert sum(by_route.values()) == 32
    assert obs.tracer.summary()["queries"] == 8


# ------------------------------------------------------------ stats schemas
def test_index_and_driver_stats_schema_exact():
    obs = Observability.create(trace_sample_every=1)
    x = _data()
    idx = _dyn(obs=obs).build(x[:256])
    for lo in range(256, 512, 64):
        idx.insert(x[lo:lo + 64])       # freezes + scheduled merges
    st = idx.index_stats()
    assert set(st) == INDEX_STATS_KEYS
    assert set(st["work_seconds"]) == WORK_PHASE_KEYS

    drv = CompactionDriver(idx)         # inherits idx.obs
    drv.start()
    try:
        drv.flush()
        ds = drv.stats()
    finally:
        drv.stop()
    assert set(ds) == DRIVER_STATS_KEYS
    # one measurement, two surfaces: the driver reports the index's dict
    assert ds["work_seconds"] == idx.index_stats()["work_seconds"]
    assert ds["work_seconds"]["total"] > 0
    kinds = obs.events.counts_by_kind()
    assert kinds.get("freeze", 0) >= 2
    assert kinds.get("swap", 0) >= 1
    assert kinds.get("driver_start") == 1 and kinds.get("driver_stop") == 1
    assert kinds.get("flush_barrier", 0) >= 1


def test_sharded_stats_schema_exact():
    import jax
    from repro.streaming import ShardedDynamicHybridIndex
    mesh = jax.make_mesh((1,), ("data",))
    obs = Observability.create()
    idx = ShardedDynamicHybridIndex(
        make_family("l2", d=D, L=L, r=1.0), mesh=mesh, num_buckets=256,
        m=32, cap=256, delta_capacity=128, key=0, obs=obs)
    idx.build(_data(256))
    st = idx.index_stats()
    assert set(st) == INDEX_STATS_KEYS | SHARDED_INDEX_EXTRA_KEYS
    assert set(st["work_seconds"]) == WORK_PHASE_KEYS


# ----------------------------------------------------------- import hygiene
def test_no_repro_module_imports_deprecated_router():
    """New code must import repro.core.engine, not the core.router shim."""
    src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    offenders = []
    for p in src.rglob("*.py"):
        if p.name == "router.py" and p.parent.name == "core":
            continue                    # the shim itself
        text = p.read_text()
        if re.search(r"from\s+repro\.core\.router\s+import|"
                     r"from\s+repro\.core\s+import\s+router\b|"
                     r"import\s+repro\.core\.router\b|"
                     r"from\s+\.router\s+import", text):
            offenders.append(str(p.relative_to(src)))
    assert offenders == []
