"""System under test: ``RetrievalService``, the served path.

A request is a few token documents.  Callers ``submit`` requests; one
``drain_batches`` forms a coalesced batch of every queued request,
serves repeats from the version-keyed result cache, embeds the misses
with the encoder in one dispatch, runs the hybrid index over the batch
and scatters each request's answers back.  A request is done when the
drain that served it returns.

The deployment comes from the configuration and ``data_seed``: the
encoder's random weights, and a corpus of token documents of three
kinds, in an order drawn from the seed (external id == row):

* templates: ``templates`` shared prefixes of ``template_prefix``
  tokens, each document a new draw of the rest (the dense region);
* families of ``family_size`` near-duplicates: a base document with its
  last ``family_edit`` tokens redrawn;
* unique documents, every token drawn.

``--seed`` draws the requests: a ``dense`` row is a new draw of a
template's tail, a ``sparse`` row a new edit of a family's base, a
``fresh`` row a new document.  Every ``1 / repeat_share``-th request of
the pool (from ``REPEAT_LAG_BATCHES`` rounds of callers on) resubmits
the request that many positions earlier, so it should be a cache hit.
The encoder itself turns these edits into near-duplicates and a dense
region (PERF.md section 4).

The reference checks the vectors the timed path used: each served
request keeps its batch's device embeddings and its row offset
(``RequestResult.embeddings``, ``row_offset``), fetched only after the
window; ``close`` keeps the index's corpus rows on the device for it,
with the service's embeddings of a sample of the corpus computed again
(``Corpus.stored``), so the reference can hold the rows to them.
"""

import dataclasses
import time
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.engine import packed_length, partition_indices
from repro.models import ParallelConfig, init_params
from repro.serve import RetrievalConfig, RetrievalService
from repro.serve.retrieval import RequestResult

REPEAT_LAG_BATCHES = 4      # a repeat resubmits a request this many rounds old
WARM_ROUNDS = 2             # rounds of callers served in the warm-up


def arch_config(cfg: Dict):
    """The encoder's ``ArchConfig``: the named architecture at the
    configuration's widths and depth."""
    layers = int(cfg["num_hidden_layers"])
    return dataclasses.replace(
        get_config(cfg["encoder"]), n_layers=layers, repeats=layers,
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]), mlp_act=cfg["hidden_act"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"])


def weights(cfg: Dict):
    """The encoder's random weights, drawn from ``data_seed`` (the one
    draw the reference shares)."""
    return init_params(arch_config(cfg),
                       jax.random.PRNGKey(int(cfg["data_seed"])))


@dataclasses.dataclass
class Corpus:
    """The deployment's documents; ``vectors`` are the index's rows
    (device, external-id order), set by ``System.close`` (or by the
    reference's control); ``stored`` (ids, (k, d) host rows) the
    service's embeddings of whole ``doc_batch`` batches drawn from
    ``data_seed``, computed again by ``System.close``."""
    tokens: np.ndarray              # (docs, doc_len) int32
    prefixes: np.ndarray            # (templates, template_prefix) int32
    bases: np.ndarray               # (families, doc_len) int32
    vectors: Optional[jax.Array] = None
    stored: Optional[tuple] = None


def corpus(cfg: Dict) -> Corpus:
    """The documents of ``data_seed``, in an order drawn from it."""
    rng = np.random.default_rng([int(cfg["data_seed"]), 11])
    n, s, v = int(cfg["docs"]), int(cfg["doc_len"]), int(cfg["vocab_size"])
    n_tpl, n_fam = int(cfg["templates"]), int(cfg["family_size"])
    per_tpl = int(cfg["template_frac"] * n) // n_tpl
    families = int(cfg["family_frac"] * n) // n_fam
    pre, edit = int(cfg["template_prefix"]), int(cfg["family_edit"])
    prefixes = rng.integers(0, v, (n_tpl, pre), dtype=np.int32)
    tpl = np.concatenate(
        [np.repeat(prefixes, per_tpl, axis=0),
         rng.integers(0, v, (n_tpl * per_tpl, s - pre), dtype=np.int32)], 1)
    bases = rng.integers(0, v, (families, s), dtype=np.int32)
    fam = np.repeat(bases, n_fam, axis=0)
    fam[:, s - edit:] = rng.integers(0, v, (len(fam), edit), dtype=np.int32)
    rest = n - len(tpl) - len(fam)
    uniq = rng.integers(0, v, (rest, s), dtype=np.int32)
    docs = np.concatenate([tpl, fam, uniq])[rng.permutation(n)]
    return Corpus(tokens=docs, prefixes=prefixes, bases=bases)


def draw_rows(rng, kinds: np.ndarray, names: List[str], docs: Corpus,
              cfg: Dict) -> np.ndarray:
    """(len(kinds), doc_len) token rows, row i of kind ``names[kinds[i]]``:
    ``dense`` a template's prefix and a new tail, ``sparse`` a family's
    base with a new edit, ``fresh`` every token new."""
    s, v = int(cfg["doc_len"]), int(cfg["vocab_size"])
    pre, edit = int(cfg["template_prefix"]), int(cfg["family_edit"])
    kinds = np.asarray(kinds)
    out = rng.integers(0, v, (len(kinds), s), dtype=np.int32)
    unknown = set(names[k] for k in np.unique(kinds)) - {"dense", "sparse",
                                                         "fresh"}
    if unknown:
        raise ValueError(f"no row kind {sorted(unknown)}")
    for name, src, keep in (("dense", docs.prefixes, pre),
                            ("sparse", docs.bases, s - edit)):
        rows = np.nonzero(kinds == names.index(name))[0] \
            if name in names else np.zeros(0, int)
        pick = rng.integers(0, len(src), len(rows))
        out[rows, :keep] = src[pick, :keep]
    return out


@dataclasses.dataclass
class Query:
    """One request of the pool and, once served, how it was served."""
    tokens: np.ndarray              # (rows, doc_len) int32
    corpus: Corpus
    kinds: tuple = ()               # each row's kind
    repeat_of: int = -1             # the pool request it resubmits
    result: Optional[RequestResult] = None
    root: Optional["Query"] = None  # a cache hit: the request first served
    vectors: Optional[np.ndarray] = None    # (rows, d) as searched

    @property
    def cached(self) -> bool:
        return self.result is not None and self.result.cached

    @property
    def served_by(self) -> "Query":
        """The request whose uncached answer this one's came from."""
        return self.root if self.cached else self


class Inputs(NamedTuple):
    requests: List[Query]           # the pool
    r: float                        # the radius every request asks for
    linear: np.ndarray              # (pool, rows) bool, see ``inputs``
    corpus: Corpus
    warm: np.ndarray                # (requests, rows, doc_len) warm-up
    params: Dict                    # the encoder's weights


def repeats(plan) -> np.ndarray:
    """(pool,) the pool request each request resubmits, -1 for none."""
    period = int(round(1.0 / float(plan.loop_params["repeat_share"])))
    lag = REPEAT_LAG_BATCHES * plan.callers
    j = np.arange(plan.pool)
    return np.where((j >= lag) & (j % period == period - 1), j - lag, -1)


def pool_tokens(seed: int, cfg: Dict, plan, docs: Corpus,
                stream: int, n: int) -> np.ndarray:
    """(n, rows, doc_len) requests drawn from ``seed``, with the plan's
    row kinds (cycled past the pool)."""
    rng = np.random.default_rng([int(seed), stream])
    kinds = plan.kinds[np.arange(n) % plan.pool]
    return draw_rows(rng, kinds.ravel(), plan.kind_names, docs,
                     cfg).reshape(kinds.shape + (-1,))


def radius(cfg: Dict, embed, docs: Corpus) -> float:
    """The rule in ``radius_rule``: the median cosine distance between
    fresh draws of a template and fresh documents of the same template,
    over one ``doc_batch`` of them, embedded by ``embed``."""
    rng = np.random.default_rng([int(cfg["data_seed"]), 12])
    n_tpl = int(cfg["templates"])
    per = int(cfg["doc_batch"]) // n_tpl
    n_q = per // 8
    rows = np.concatenate([draw_rows(
        rng, np.zeros(per, int), ["dense"],
        dataclasses.replace(docs, prefixes=docs.prefixes[t:t + 1]), cfg)
        for t in range(n_tpl)])
    e = embed({"tokens": jnp.asarray(rows)})
    d = np.asarray(1.0 - jnp.matmul(e, e.T,
                                    precision=jax.lax.Precision.HIGHEST))
    d = d.reshape(n_tpl, per, n_tpl, per)
    return float(np.median(np.stack([d[t, :n_q, t, n_q:]
                                     for t in range(n_tpl)])))


def inputs(seed: int, cfg: Dict, plan) -> Inputs:
    """The pool, the radius, the corpus, the warm-up requests and the
    weights of one seed.  ``linear``: the rows the control answers as
    the linear route would serve them, the dense ones: a template draw's
    probed buckets hold more of its template's documents than the LSH
    route gathers (the cap), so the router sends it linear; the others
    overflow a bucket only where a template's documents share it by
    chance, and mostly keep the LSH route."""
    docs = corpus(cfg)
    params = weights(cfg)
    embed = RetrievalService(arch_config(cfg), ParallelConfig(), params).embed
    r = radius(cfg, embed, docs)
    toks = pool_tokens(seed, cfg, plan, docs, 13, plan.pool)
    rep = repeats(plan)
    src = np.arange(plan.pool)
    for j in np.nonzero(rep >= 0)[0]:      # a repeat of a repeat: its root
        src[j] = src[rep[j]]
    toks = toks[src]
    pool = [Query(tokens=toks[j], corpus=docs, repeat_of=int(rep[j]),
                  kinds=tuple(plan.kind_names[k] for k in plan.kinds[src[j]]))
            for j in range(plan.pool)]
    warm = pool_tokens(seed, cfg, plan, docs, 14,
                       WARM_ROUNDS * plan.callers)
    linear = (plan.kinds[src] == plan.kind_names.index("dense")
              if "dense" in plan.kind_names
              else np.zeros(plan.kinds.shape, bool))
    return Inputs(pool, r, linear, docs, warm, params)


class System:
    """The service over the deployment's corpus, the pool and r."""

    def __init__(self, cfg: Dict, seed: int, plan, log=None):
        fields = {f.name for f in dataclasses.fields(RequestResult)}
        if not {"embeddings", "row_offset", "linear"} <= fields:
            # the reference needs the vectors the timed path searched
            raise RuntimeError("RequestResult does not say how an answer "
                               "was computed: the served path is too old")
        self.cfg = cfg
        self.log = log = log or (lambda msg: None)
        t = time.perf_counter()

        def phase(name):
            nonlocal t
            log(f"  set-up {name}: {time.perf_counter() - t:.3f} s")
            t = time.perf_counter()

        inp = inputs(seed, cfg, plan)
        phase("weights, documents, requests, radius")
        self.r, self.requests, self.warm = inp.r, inp.requests, inp.warm
        self.corpus = inp.corpus
        self.svc = RetrievalService(
            arch_config(cfg), ParallelConfig(), inp.params, RetrievalConfig(
                radius=self.r,          # sizes the SimHash family
                tables=int(cfg["tables"]), cap=int(cfg["cap"]),
                num_buckets=int(cfg["num_buckets"]), hll_m=int(cfg["hll_m"]),
                beta_over_alpha=float(cfg["beta_over_alpha"]),
                delta=float(cfg["lsh_delta"]),
                delta_capacity=int(cfg["delta_capacity"])))
        del inp
        b = int(cfg["doc_batch"])
        n = self.svc.index_corpus(
            {"tokens": jnp.asarray(self.corpus.tokens[lo:lo + b])}
            for lo in range(0, len(self.corpus.tokens), b))
        if n != len(self.corpus.tokens):
            raise RuntimeError(f"indexed {n} of {len(self.corpus.tokens)}")
        phase("corpus embedded and indexed")
        self._inflight: Dict[int, Query] = {}
        self.rounds = 0

    # ------------------------------------------------------------ serving
    def submit(self, i: int) -> int:
        """Enqueue pool request ``i``; returns its uid."""
        return self._submit(self.requests[i])

    def _submit(self, q: Query) -> int:
        uid = self.svc.submit({"tokens": q.tokens}, radius=self.r)
        if uid is None:
            raise RuntimeError("admission control shed a request")
        self._inflight[uid] = q
        return uid

    def drain(self) -> Dict[int, tuple]:
        """Serve every queued request; returns uid -> (ids per row,
        counts of the request)."""
        out = self.svc.drain_batches()
        self.rounds += 1
        answers = {}
        for uid, res in out.items():
            q = self._inflight.pop(uid)
            q.result = res
            if res.cached:
                q.root = self._root(q)
            answers[uid] = (res.ids, {
                "rows": res.n_queries, "cached": res.cached,
                "linear": q.served_by.result.linear, "round": self.rounds})
        return answers

    def _root(self, q: Query) -> Query:
        while q.repeat_of >= 0:
            q = self.requests[q.repeat_of]
        if q.result is None or q.result.cached:
            raise RuntimeError("a cache hit on a request never served")
        return q

    def warmup(self, rec) -> int:
        """Serve ``WARM_ROUNDS`` rounds of requests drawn apart from the
        pool, as the window serves them, then query the index with
        every split of a batch between the routes the window can take;
        returns how many requests were served.  They leave no window
        request in the cache."""
        callers = len(self.warm) // WARM_ROUNDS
        warmed = []
        for k in range(WARM_ROUNDS):
            for toks in self.warm[k * callers:(k + 1) * callers]:
                q = Query(tokens=toks, corpus=self.corpus)
                self._submit(q)
                warmed.append(q)
            self.drain()
        self._warm_splits([q.result for q in warmed])
        self.rounds = 0
        return len(self.warm)

    def _warm_splits(self, results: List[RequestResult]) -> None:
        """Compile the search and the extraction of a batch split
        between the routes in every way the window can split it: for
        each pair of route group sizes (powers of two,
        ``partition_indices``) its most linear rows, from query vectors
        the warm-up saw routed each way.  The linear rows mix the one
        with the fewest ids and the one with the most, in every
        proportion that packs their answers to another length
        (``packed_length``): a batch's padding repeats its last row, so
        the window's linear answers take more than one length."""
        lin, lsh = [], []
        for res in results:
            emb = res.embeddings[res.row_offset:res.row_offset + res.n_queries]
            for j in range(res.n_queries):
                if res.linear[j]:
                    lin.append((len(res.ids[j]), j, emb[j]))
                else:
                    lsh.append(emb[j])
        lin.sort(key=lambda p: p[:2])
        rows = results[0].embeddings.shape[0]    # a batch, padding included
        splits = {}
        for n_lin in range(rows + 1):
            groups = partition_indices(np.arange(rows) >= n_lin)
            splits[tuple(len(g) for g in groups)] = n_lin
        for n_lin in sorted(splits.values()):
            if (n_lin and not lin) or (n_lin < rows and not lsh):
                continue
            seen = set()
            for most in range(n_lin + 1):
                total = (most * lin[-1][0] + (n_lin - most) * lin[0][0]
                         if n_lin else 0)
                if packed_length(total) in seen:
                    continue
                seen.add(packed_length(total))
                q = ([lin[-1][2]] * most + [lin[0][2]] * (n_lin - most)
                     if n_lin else []) \
                    + [lsh[i % len(lsh)] for i in range(rows - n_lin)]
                res = self.svc.index.query(jnp.stack(q), self.r)
                for i in range(rows):
                    res.reported(i)

    # ----------------------------------------------------------- counting
    def work_counts(self, served: List) -> Dict[str, float]:
        """The algorithm's work over ``served`` [(request, counts)]: the
        encoder's calls and tokens (the rows not served from the cache),
        its shape, and the LSH route's rows and distinct candidates in
        the frozen segments (cap-truncated), from the query vectors the
        index searched."""
        from chipbench import harness
        distinct = harness.load_module(
            harness.BENCH / "systems" / "dynamic_index.py"
        )._distinct_candidates
        arch = arch_config(self.cfg)
        idx = self.svc.index
        miss = [(self.requests[i], c) for i, c in served if not c["cached"]]
        lin_miss = [(q, c) for q, c in miss if c["linear"].any()]
        lsh_q = [q.result.embeddings[q.result.row_offset:
                                     q.result.row_offset + c["rows"]]
                 [~c["linear"]] for q, c in miss]
        lsh_q = jnp.concatenate(lsh_q) if lsh_q else jnp.zeros((0, 1))
        cand = 0
        for lo in range(0, lsh_q.shape[0], 4096):
            qb = idx.family.bucket_ids(idx.params, lsh_q[lo:lo + 4096],
                                       idx.num_buckets)
            for f in idx.stack.segments:
                t = f.seg.tables
                cand += int(jnp.sum(distinct(t.perm, t.starts, qb,
                                             cap=idx.cap)))
        return {
            "dim": float(arch.d_model),
            "encoder_calls": float(len({c["round"] for _, c in miss})),
            "encoder_tokens": float(sum(c["rows"] for _, c in miss)
                                    * int(self.cfg["doc_len"])),
            "encoder_seq": float(self.cfg["doc_len"]),
            "encoder_layers": float(arch.n_layers),
            "encoder_heads": float(arch.n_heads),
            "encoder_kv_heads": float(arch.n_kv_heads),
            "encoder_head_dim": float(arch.hd),
            "encoder_ffn": float(arch.d_ff),
            "lsh_rows": float(lsh_q.shape[0]),
            "lsh_candidates": float(cand),
            "live_rows": float(idx.n),
            "linear_calls": float(len({c["round"] for _, c in lin_miss})),
            "linear_rows": float(sum(int(c["linear"].sum())
                                     for _, c in lin_miss)),
            "linear_pairs": float(sum(
                len(ids) for q, c in lin_miss
                for ids, on in zip(q.result.ids, c["linear"]) if on)),
        }

    def _summary(self, served: List[Query]) -> None:
        """Log what the window's rows reported, by kind, and the
        service's route and cache counters."""
        by_kind: Dict[str, List[int]] = {}
        for q in served:
            for kind, ids in zip(q.kinds, q.result.ids):
                by_kind.setdefault(kind, []).append(len(ids))
        st = self.svc.stats
        self.log(f"radius {self.r:.6g}; reported ids a row, median (max): "
                 + ", ".join(f"{k} {np.median(v):g} ({max(v)})"
                             for k, v in sorted(by_kind.items()))
                 + f"; rows {st['queries']}, linear {st['linear_served']}; "
                 f"cache hits {st['cache']['hits']}, misses "
                 f"{st['cache']['misses']}; batches "
                 f"{st['scheduler']['batches']}")

    def close(self) -> None:
        """Bring the searched query vectors to the host, keep the
        index's rows on the device for the reference (external-id
        order), and free the service."""
        idx = self.svc.index
        if int(idx.delta.count):
            raise RuntimeError("the corpus left rows in the delta")
        xs, ids = [], []
        for f in idx.stack.segments:
            keep = np.nonzero(np.asarray(f.seg.ids) >= 0)[0]
            xs.append(f.seg.x[keep])
            ids.append(np.asarray(f.seg.ids)[keep])
        ids = np.concatenate(ids)
        x = jnp.concatenate(xs) if len(xs) > 1 else xs[0]
        if not np.array_equal(ids, np.arange(len(ids))):
            x = x[jnp.asarray(np.argsort(ids))]
        served = [q for q in self.requests if q.result is not None
                  and not q.result.cached]
        arrays = {id(q.result.embeddings): q.result.embeddings
                  for q in served}
        host = dict(zip(arrays, jax.device_get(list(arrays.values()))))
        for q in served:
            res = q.result
            q.vectors = host[id(res.embeddings)][
                res.row_offset:res.row_offset + res.n_queries]
            q.result = dataclasses.replace(res, embeddings=None)
        self._summary(served)
        self.corpus.stored = self._embed_again()
        self.svc.shutdown(flush=False)
        self.svc = None
        self.corpus.vectors = x

    def _embed_again(self) -> tuple:
        """(ids, (k, d) host rows): the service's embeddings of whole
        ``doc_batch`` batches of the corpus, ``stored_sample`` documents
        drawn from ``data_seed``, computed again as ``index_corpus``
        computed them."""
        b = int(self.cfg["doc_batch"])
        n_batches = len(self.corpus.tokens) // b
        rng = np.random.default_rng([int(self.cfg["data_seed"]), 16])
        pick = np.sort(rng.choice(
            n_batches, min(int(self.cfg["stored_sample"]) // b, n_batches),
            replace=False))
        ids = np.concatenate([np.arange(k * b, (k + 1) * b) for k in pick])
        rows = [np.asarray(self.svc.embed(
            {"tokens": jnp.asarray(self.corpus.tokens[k * b:(k + 1) * b])}))
            for k in pick]
        return ids, np.concatenate(rows)
