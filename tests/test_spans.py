"""Profiler spans on the index's query and extraction path.

A CPU profile of ``DynamicHybridIndex.query`` plus ``reported`` holds
the ``repro.*`` spans docs/observability.md lists, nested by time on
the caller's thread, with the counts they carry computed from shapes:
four device reads a request, two of them the one compacted extraction.
Profiling changes no answer.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import CostModel
from repro.core.engine import packed_length
from repro.core.lsh import make_family
from repro.streaming import CompactionPolicy, DynamicHybridIndex

D, L, Q = 8, 4, 12

# each span's parent: the innermost other span that covers it
PARENT = {
    "repro.index.query": None,
    "repro.index.hash": "repro.index.query",
    "repro.index.delta_count.sync": "repro.index.query",
    "repro.engine.estimate": "repro.index.query",
    "repro.engine.route.sync": "repro.index.query",
    "repro.engine.search": "repro.index.query",
    "repro.engine.segment": "repro.engine.search",
    "repro.result.reported": None,
    "repro.result.copy.sync": "repro.result.reported",
    "repro.result.compact": "repro.result.copy.sync",
}


def _index():
    rng = np.random.default_rng(0)
    # a tight cluster (linear route) and spread rows (LSH route)
    x = np.concatenate([rng.normal(size=(256, D)) * 0.05,
                        rng.normal(size=(256, D)) * 3.0]).astype(np.float32)
    idx = DynamicHybridIndex(
        make_family("l2", d=D, L=L, r=1.0), num_buckets=256, m=32, cap=256,
        key=0, delta_capacity=128, cost_model=CostModel(alpha=1.0, beta=1.0),
        policy=CompactionPolicy(delta_fill=1.0, tombstone_ratio=2.0,
                                fanout=2))
    idx.build(x[:384])
    idx.insert(x[384:])                 # a freeze: frozen levels + delta
    idx.delete([3, 300])
    return idx, jnp.asarray(x[::40][:Q])


def _serve(idx, q):
    res = idx.query(q, 1.2)
    return res, [res.reported(i) for i in range(Q)]


def _profiled(tmp_path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    spans = [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
              dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _parent(span, spans):
    cover = [s for s in spans if s is not span
             and s[1] <= span[1] and span[2] <= s[2]]
    return min(cover, key=lambda s: s[2] - s[1])[0] if cover else None


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    idx, q = _index()
    _serve(idx, q)                      # compile outside the profile
    (res, answers), spans = _profiled(tmp_path_factory.mktemp("prof"),
                                      lambda: _serve(idx, q))
    return idx, res, answers, spans


def test_spans_nest_as_listed(profiled):
    _, _, _, spans = profiled
    assert {s[0] for s in spans} == set(PARENT)
    for s in spans:
        assert _parent(s, spans) == PARENT[s[0]], s


def test_span_counts_from_shapes(profiled):
    idx, res, answers, spans = profiled
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s[3])
    n_seg = len(idx.stack.segments) + 1
    assert n_seg >= 2
    top, = by["repro.index.query"]
    assert top == {"batch": res.batch, "rows": Q}
    assert by["repro.engine.estimate"] == [{"batch": res.batch,
                                            "segments": n_seg}]
    assert by["repro.index.delta_count.sync"] == [{"batch": res.batch,
                                                   "reads": 1}]
    sync, = by["repro.engine.route.sync"]
    use = np.asarray(res.route.use_lsh)
    assert sync == {"batch": res.batch, "reads": 1,
                    "lsh_rows": int(use.sum()),
                    "linear_rows": int((~use).sum())}

    groups = {"lsh": (res.lsh_idx, res.lsh_out),
              "linear": (res.lin_idx, res.lin_out)}
    searched = {a["route"]: a for a in by["repro.engine.search"]}
    assert set(searched) == {r for r, (_, o) in groups.items()
                             if o is not None}
    assert set(searched) == {"lsh", "linear"}       # both routes ran
    for route, a in searched.items():
        idx_r, out = groups[route]
        assert a == {"batch": res.batch, "route": route,
                     "rows": len(set(np.asarray(idx_r).tolist())),
                     "padded_rows": len(idx_r),
                     "width": out[0].shape[-1]}
    segs = sorted((a["route"], a["segment"])
                  for a in by["repro.engine.segment"])
    assert segs == sorted((r, i) for r in searched for i in range(n_seg))

    reported = by["repro.result.reported"]
    assert len(reported) == Q
    pairs = [sum(len(ids) for (ids, _), u in zip(answers, use) if u == lsh)
             for lsh in (True, False)]                 # LSH, linear group
    total, padded = sum(pairs), sum(packed_length(p) for p in pairs)
    for i, a in enumerate(reported):
        route = "lsh" if use[i] else "linear"
        # the first call moves the whole result: the per-query counts,
        # then each group's packed ids and distances; every later call
        # slices it
        moved = Q * 4 + padded * (4 + 4) if i == 0 else 0
        assert a == {"batch": res.batch, "route": route,
                     "reported": len(answers[i][0]), "bytes": moved}
    assert by["repro.result.copy.sync"] == [{"batch": res.batch,
                                             "reads": 2}]
    assert by["repro.result.compact"] == [{"batch": res.batch,
                                           "pairs": total,
                                           "padded_pairs": padded}]
    # every device read of the request: the delta count, the route
    # choice, then the counts and the packed pairs of the whole result
    reads = sum(s[3]["reads"] for s in spans if s[0].endswith(".sync"))
    assert reads == 4


def test_profiling_changes_no_answer(profiled, tmp_path):
    idx, q = _index()
    _, plain = _serve(idx, q)
    (_, traced), _ = _profiled(tmp_path, lambda: _serve(idx, q))
    assert len(plain) == len(traced) == Q
    for (ids0, d0), (ids1, d1) in zip(plain, traced):
        np.testing.assert_array_equal(ids0, ids1)
        np.testing.assert_array_equal(d0, d1)
    _, _, answers, _ = profiled
    assert [set(a.tolist()) for a, _ in answers] == \
        [set(a.tolist()) for a, _ in plain]


# the served path: each span's parent, as above
SERVED_PARENT = {
    "repro.scheduler.next_batch": None,
    "repro.service.batch": None,
    "repro.service.cache": "repro.service.batch",
    "repro.service.embed": "repro.service.batch",
    "repro.index.query": "repro.service.batch",
}
DOC_LEN = 8


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A round of three 2-row requests through ``submit`` ->
    ``drain_batches``, profiled: one repeats a request of the round
    before (a cache hit), two are new (one miss group of 4 rows, padded
    to 8)."""
    from repro.configs import get_config, reduced_config
    from repro.models import ParallelConfig, init_params
    from repro.serve import RetrievalConfig, RetrievalService

    arch = reduced_config(get_config("yi-6b"))
    params = init_params(arch, jax.random.PRNGKey(0))
    svc = RetrievalService(arch, ParallelConfig(), params, RetrievalConfig(
        radius=0.5, num_buckets=64, delta_capacity=64))
    docs = np.random.default_rng(0).integers(0, arch.vocab, (80, DOC_LEN),
                                             dtype=np.int32)
    svc.index_corpus([{"tokens": jnp.asarray(docs[:64])}])
    reqs = [docs[64 + 2 * i:66 + 2 * i] for i in range(4)]

    def round_(batch):
        for r in batch:
            svc.submit({"tokens": r})
        return svc.drain_batches()

    round_(reqs[:2])                    # compile; caches the first two
    out, spans = _profiled(tmp_path_factory.mktemp("served"),
                           lambda: round_([reqs[0]] + reqs[2:]))
    return svc, arch, params, out, spans


def test_served_spans_nest_as_listed(served):
    *_, spans = served
    assert set(SERVED_PARENT) <= {s[0] for s in spans}
    for s in spans:
        if s[0] in SERVED_PARENT:
            assert _parent(s, spans) == SERVED_PARENT[s[0]], s


def test_served_span_counts(served):
    svc, arch, params, out, spans = served
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s[3])
    assert [r.cached for r in out.values()] == [True, False, False]
    # the drain forms one batch, then finds the queue empty
    formed, empty = by["repro.scheduler.next_batch"]
    assert {k: formed[k] for k in ("queued", "formed")} == \
        {"queued": 3, "formed": 3}
    assert formed["max_wait_ms"] >= 0.0
    assert empty == {"queued": 0, "formed": 0, "max_wait_ms": 0.0}
    assert by["repro.service.batch"] == [{"requests": 3, "rows": 6,
                                          "padded_rows": 8, "groups": 1}]
    assert by["repro.service.cache"] == [{"hits": 1, "misses": 2}]
    assert by["repro.service.embed"] == [{"rows": 8,
                                          "tokens": 8 * DOC_LEN}]
    assert [a["rows"] for a in by["repro.index.query"]] == [8]
    # a served answer carries its route flags and its query vectors
    hit, *miss = out.values()
    assert hit.linear is None and hit.embeddings is None
    for res in miss:
        assert res.linear.shape == (2,) and res.embeddings.shape[0] == 8
    # the encoder runs as one named program
    from repro.models import ParallelConfig
    from repro.serve.retrieval import _forward_embed
    text = _forward_embed.lower(
        params, {"tokens": jnp.zeros((8, DOC_LEN), jnp.int32)}, cfg=arch,
        par=ParallelConfig()).as_text()
    assert "jit_forward_embed" in text
