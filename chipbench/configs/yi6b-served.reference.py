"""Plain reference for ``yi6b-served``: a float32 encoder and a float32
brute-force cosine range search.

``encode`` is the published block (Yi-6B's ``config.json``; the Llama
layout of arXiv:2403.04652) written out in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: token embedding, then per
layer RMSNorm, grouped-query causal attention (rotary embedding, rotate
half, base ``rope_theta``; each key/value head serves ``heads /
kv_heads`` query heads), residual, RMSNorm, the SiLU-gated MLP,
residual; then the final RMSNorm, the mean over the document's tokens
and an L2 normalisation.  Departures from the published model: no LM
head (an embedding is read, not a token), the pooling is the
deployment's, and only ``num_hidden_layers`` layers run.  It takes the
service's weights as they are drawn (``systems/retrieval_service.py``:
``weights``), cast from bfloat16 to float32; nothing else of the program
is used.

The harness loads this file anew for every run, so its jitted
functions compile anew: they take fixed shapes (``ENCODE_ROWS`` rows at
a time), a handful of compiles a run.

``check`` compares a sample of the window's requests, each a ``Query``
of the adapter that carries the vectors the timed path searched (its
query embeddings, and the index's rows on the corpus), and returns five
numbers, held to ``LIMITS``:

``embed_deviation``
  max(1 - cos) between the reference's embedding and the one the timed
  path used, over the checked query rows, ``embed_sample`` documents
  drawn from ``data_seed`` and every document reported to a checked row.
  The service computes in bfloat16 (the configuration's precision); the
  limit is set above what that gives and below what weights cast to
  float8 give (PERF.md section 2).
``wrong_pairs`` and ``lsh_recall_shortfall``
  As ``hybridlsh-densecore-l2``'s (``compare_row``), over cosine
  distances ``1 - q.x / (|q| |x|)`` in float32 between each checked row's
  query vector and every document's: documents within ``deep_frac * r``
  are deep, and the recall at r is SimHash's ``1 - (1 - p(r)^k)^tables``
  with ``p(r) = 1 - arccos(1 - r) / pi`` and ``k`` the paper's (the
  smallest with ``(1 - p(r)^k)^tables <= lsh_delta``).  Documents within
  float32 reach of r (``|d - r| <= 1e-5 (|q|^2 + |x|^2) / 2``, the L2
  band of densecore on unit vectors) count either way.  The deep and
  recall checks hold every LSH row with at most ``deep_max_reference``
  documents within r, the whole corpus: the router sends a row linear
  when its probed buckets lose more than one bucket's worth of
  documents to the cap the LSH route gathers (a template row), and a
  row that loses less is held to the recall all the same.
``stored_row_mismatch``
  Documents whose row in the index differs, in any bit, from the
  service's embedding of the document computed again after the window
  (``Corpus.stored``: whole ``doc_batch`` batches drawn from
  ``data_seed``), or every sampled document if the index keeps its rows
  in another dtype than float32.  So the corpus vectors the range search
  reads are those the encoder gave, which the sample of
  ``embed_deviation`` holds to the float32 reference.
``cache_mismatch_rows``
  Rows of a request served from the result cache whose ids differ from
  the uncached answer of the request first served with its tokens.

``control`` is the reference in bfloat16 in the program's place: it
embeds the rows and the corpus with ``encode`` in bfloat16 (the
configuration's precision, so ``embed_deviation`` stays low) and answers
by bfloat16 cosine in the rows it is told to lower (the precision below
the search's float32): its answers must fail ``check``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness

LIMITS = {"wrong_pairs": 0, "lsh_recall_shortfall": 0.08,
          "embed_deviation": 2e-4, "cache_mismatch_rows": 0,
          "stored_row_mismatch": 0}
BAND = 1e-5
ENCODE_ROWS = 256          # documents the reference encodes at a time

_DENSE = harness.load_module(harness.BENCH / "configs"
                             / "hybridlsh-densecore-l2.reference.py")
OUTSIDE, INSIDE, IN_BAND, DEEP = (_DENSE.OUTSIDE, _DENSE.INSIDE,
                                  _DENSE.IN_BAND, _DENSE.DEEP)
compare_row, WRONG = _DENSE.compare_row, _DENSE.WRONG


def simhash_recall_at_r(cfg: Dict, r: float) -> float:
    """The share of documents at cosine distance r that ``tables``
    SimHash tables of the paper's ``k`` find."""
    p = 1.0 - math.acos(max(-1.0, min(1.0, 1.0 - r))) / math.pi
    n_tables, delta = int(cfg["tables"]), float(cfg["lsh_delta"])
    k = max(1, math.ceil(math.log(1.0 - delta ** (1.0 / n_tables))
                         / math.log(p)))
    return 1.0 - (1.0 - p ** k) ** n_tables


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary embedding of (B, S, H, hd) at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta"))
def _encode(w, tokens, *, heads, kv_heads, eps, theta):
    """(B, S) tokens -> (B, D) float32 unit embeddings, in the dtype of
    the weights ``w``."""
    h = w["embed"][tokens]
    b, s, _ = h.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    for p in w["layers"]:
        x = _rmsnorm(h, p["norm1"], eps)
        q = (x @ p["wq"]).reshape(b, s, heads, -1)
        k = (x @ p["wk"]).reshape(b, s, kv_heads, -1)
        v = (x @ p["wv"]).reshape(b, s, kv_heads, -1)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=2)
        v = jnp.repeat(v, heads // kv_heads, axis=2)
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, -1)
        h = h + o @ p["wo"]
        x = _rmsnorm(h, p["norm2"], eps)
        h = h + (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wd"]
    e = jnp.mean(_rmsnorm(h, w["final_norm"], eps).astype(jnp.float32), 1)
    return e / jnp.linalg.norm(e, axis=-1, keepdims=True)


def encoder_weights(params, dtype) -> Dict:
    """The service's weight tree, per layer and in ``dtype``."""
    blk = params["blocks"][0]
    c = lambda a: jnp.asarray(a, dtype)           # noqa: E731
    return {"embed": c(params["embed"]),
            "final_norm": c(params["final_norm"]),
            "layers": [{"norm1": c(blk["norm1"][i]),
                        "norm2": c(blk["norm2"][i]),
                        "wq": c(blk["attn"]["wq"][i]),
                        "wk": c(blk["attn"]["wk"][i]),
                        "wv": c(blk["attn"]["wv"][i]),
                        "wo": c(blk["attn"]["wo"][i]),
                        "wi": c(blk["mlp"]["wi"][i]),
                        "wg": c(blk["mlp"]["wg"][i]),
                        "wd": c(blk["mlp"]["wo"][i])}
                       for i in range(blk["norm1"].shape[0])]}


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta"))
def _deviation(w, tokens, got, **kw):
    """max(1 - cos) of ``got`` against the embeddings of ``tokens``."""
    want = _encode(w, tokens, **kw)
    cos = jnp.sum(got * want, -1) / (jnp.linalg.norm(got, axis=-1)
                                     * jnp.linalg.norm(want, axis=-1))
    return jnp.max(1.0 - cos)


def _padded(a: np.ndarray) -> np.ndarray:
    """``a`` with its last row repeated up to ``ENCODE_ROWS`` rows."""
    return np.concatenate([a, np.repeat(a[-1:], ENCODE_ROWS - len(a), 0)])


def _encoder_args(cfg: Dict) -> Dict:
    return dict(heads=int(cfg["num_attention_heads"]),
                kv_heads=int(cfg["num_key_value_heads"]),
                eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]))


def encode(cfg: Dict, w: Dict, tokens: np.ndarray) -> jax.Array:
    """(n, S) tokens -> (n, D) float32 embeddings on the device,
    ``ENCODE_ROWS`` documents at a time, matmuls at full precision."""
    out = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(tokens), ENCODE_ROWS):
            chunk = np.asarray(tokens[lo:lo + ENCODE_ROWS], np.int32)
            e = _encode(w, jnp.asarray(_padded(chunk)), **_encoder_args(cfg))
            out.append(e[:len(chunk)] if len(chunk) < ENCODE_ROWS else e)
    return jnp.concatenate(out) if len(out) > 1 else out[0]


def deviation(cfg: Dict, w: Dict, tokens: np.ndarray, got) -> float:
    """max(1 - cos) of the (n, D) embeddings ``got`` against the
    reference's of ``tokens``, ``ENCODE_ROWS`` rows at a time."""
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(tokens), ENCODE_ROWS):
            t = _padded(np.asarray(tokens[lo:lo + ENCODE_ROWS], np.int32))
            g = _padded(np.asarray(got[lo:lo + ENCODE_ROWS], np.float32))
            worst = max(worst, float(_deviation(
                w, jnp.asarray(t), jnp.asarray(g), **_encoder_args(cfg))))
    return worst


def _cosine(q, x):
    """(k, n) float32 cosine distances and the band's half-width."""
    dots = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    qq, xx = jnp.sum(q * q, -1), jnp.sum(x * x, -1)
    d = 1.0 - dots / jnp.sqrt(qq[:, None] * xx[None, :])
    return d, BAND * (qq[:, None] + xx[None, :]) / 2.0


@jax.jit
def _codes(q, x, r, deep):
    """Each row's class per document."""
    d, tol = _cosine(q, x)
    code = jnp.where(d <= r, INSIDE, OUTSIDE)
    code = jnp.where(d <= deep, DEEP, code)
    return jnp.where(jnp.abs(d - r) <= tol, IN_BAND, code).astype(jnp.int8)


@jax.jit
def _control_mask(q, x, r, low):
    """Rows with ``low`` set: bfloat16 dot products; the others: float32."""
    lo = 1.0 - jnp.matmul(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16).T,
                          preferred_element_type=jnp.float32)
    d = jnp.where(low[:, None], lo, _cosine(q, x)[0])
    return d <= r


class Reference:
    """The configuration's encoder weights (drawn on first use) and r."""

    def __init__(self, cfg: Dict, r: float):
        self.cfg = cfg
        self.r = float(r)
        self.system = harness.load_module(harness.BENCH / "systems"
                                          / f"{cfg['system']}.py")
        self._w = {}

    def weights(self, dtype) -> Dict:
        if dtype not in self._w:
            self._w[dtype] = encoder_weights(self.system.weights(self.cfg),
                                             dtype)
        return self._w[dtype]

    def encode(self, tokens: np.ndarray, dtype=jnp.float32) -> jax.Array:
        return encode(self.cfg, self.weights(dtype), tokens)

    def codes(self, q: np.ndarray, x: jax.Array) -> np.ndarray:
        """(k, n) classes of the documents for each row (the constants)."""
        deep = float(self.cfg["deep_frac"]) * self.r
        return np.asarray(_codes(jnp.asarray(q, jnp.float32), x,
                                 jnp.float32(self.r), jnp.float32(deep)))

    def control(self, query, low=None) -> List[np.ndarray]:
        """The request's rows and the corpus embedded in bfloat16 in the
        program's place, then each row's documents within r, in
        bfloat16 where ``low`` (default: every row)."""
        docs = query.corpus
        if docs.vectors is None:
            docs.vectors = self.encode(docs.tokens, jnp.bfloat16)
        query.vectors = np.asarray(self.encode(query.tokens, jnp.bfloat16))
        low = (np.ones(len(query.tokens), bool) if low is None
               else np.asarray(low, bool))
        m = np.asarray(_control_mask(jnp.asarray(query.vectors), docs.vectors,
                                     jnp.float32(self.r), jnp.asarray(low)))
        return [np.nonzero(row)[0] for row in m]


def embed_deviation(ref: Reference, sample) -> Tuple[float, int]:
    """max(1 - cos) of the timed path's vectors against the reference's,
    and how many were compared (see the module docstring)."""
    docs = sample[0][0].corpus
    n = len(docs.tokens)
    rng = np.random.default_rng([int(ref.cfg["data_seed"]), 15])
    ids = set(rng.choice(n, min(int(ref.cfg["embed_sample"]), n),
                         replace=False).tolist())
    for _, answer, _ in sample:
        for row in answer:
            ids.update(int(i) for i in row if 0 <= i < n)
    ids = np.array(sorted(ids))
    rows = [q.served_by for q, _, _ in sample]
    w = ref.weights(jnp.float32)
    worst = max(
        deviation(ref.cfg, w, docs.tokens[ids], docs.vectors[ids]),
        deviation(ref.cfg, w, np.concatenate([q.tokens for q in rows]),
                  np.concatenate([q.vectors for q in rows])))
    return worst, len(ids) + sum(len(q.tokens) for q in rows)


def stored_row_mismatch(docs) -> Tuple[int, int]:
    """Documents of ``docs.stored`` whose index row differs from the
    service's embedding computed again, and how many were compared."""
    if docs.stored is None:          # the control: no index, no rows
        return 0, 0
    ids, again = docs.stored
    if docs.vectors.dtype != jnp.float32:
        return len(ids), len(ids)
    rows = np.asarray(docs.vectors[jnp.asarray(ids)])
    return int(np.sum(np.any(rows != np.asarray(again), axis=-1))), len(ids)


def check(ref: Reference, sample: Sequence[Tuple]
          ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """``sample``: (request, reported ids per row, linear-route flag per
    row) per checked request; the request carries its vectors.  Returns
    (numbers compared, tallies)."""
    tally: Dict[str, int] = {"cached_requests": 0, "cache_mismatch_rows": 0}
    deep_max = int(ref.cfg["deep_max_reference"])
    recall = simhash_recall_at_r(ref.cfg, ref.r)
    x = sample[0][0].corpus.vectors
    for query, ids, linear in sample:
        src = query.served_by
        if query.cached:
            tally["cached_requests"] += 1
            tally["cache_mismatch_rows"] += sum(
                not np.array_equal(a, b) for a, b in zip(ids, src.result.ids))
        codes = ref.codes(src.vectors, x)
        for j in range(len(ids)):
            tally["linear_rows"] = tally.get("linear_rows", 0) + int(
                bool(linear[j]))
            for k, v in compare_row(codes[j], ids[j], bool(linear[j]),
                                    deep_max, recall).items():
                tally[k] = tally.get(k, 0) + v
    tally["rows"] = int(sum(len(ids) for _, ids, _ in sample))
    deviation, tally["embedded"] = embed_deviation(ref, sample)
    stored, tally["stored_compared"] = stored_row_mismatch(
        sample[0][0].corpus)
    within = tally.get("lsh_within", 0)
    return {"wrong_pairs": float(sum(tally.get(k, 0) for k in WRONG)),
            "lsh_recall_shortfall": (tally.get("lsh_short", 0) / within
                                     if within else 0.0),
            "embed_deviation": deviation,
            "cache_mismatch_rows": float(tally["cache_mismatch_rows"]),
            "stored_row_mismatch": float(stored)}, tally
