"""The served configuration's plain reference against the program.

``chipbench/configs/yi6b-served.reference.py`` holds a float32 encoder
written from the published block.  On seeded weights at yi-6b's block
cut to tiny widths and 4 layers, it agrees with the service's encoder
(``RetrievalService.embed``, ``forward_embed`` in bfloat16) within the
configuration's ``embed_deviation`` limit, and weights cast to float8
read above it.  A result-cache hit whose answer differs from the
uncached one reads not correct, and so does an index that keeps the
corpus's embeddings in bfloat16.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.test_chipbench_rehearsal import SEED, small
from repro.configs import get_config, reduced_config
from repro.models import ParallelConfig, init_params
from repro.models.transformer import forward_embed
from repro.serve import RetrievalService
from repro.serve.cache import ResultCache

CELL = "yi6b-served.steady"
REF = harness.load_module(harness.BENCH / "configs"
                          / "yi6b-served.reference.py")
LIMIT = REF.LIMITS["embed_deviation"]


@pytest.fixture(scope="module")
def encoder():
    """yi-6b's block at tiny widths, 4 layers; its seeded weights; the
    reference's view of the arch; 64 token documents."""
    arch = reduced_config(get_config("yi-6b"))
    arch = dataclasses.replace(arch, n_layers=4, repeats=4)
    cfg = {"num_attention_heads": arch.n_heads,
           "num_key_value_heads": arch.n_kv_heads,
           "rms_norm_eps": arch.norm_eps, "rope_theta": arch.rope_theta}
    params = init_params(arch, jax.random.PRNGKey(3))
    tokens = np.random.default_rng(3).integers(0, arch.vocab, (64, 32),
                                               dtype=np.int32)
    return arch, cfg, params, tokens


def _served(arch, params, tokens):
    svc = RetrievalService(arch, ParallelConfig(), params)
    return np.asarray(svc.embed({"tokens": jnp.asarray(tokens)}))


def test_reference_encoder_agrees_with_the_service(encoder):
    arch, cfg, params, tokens = encoder
    assert arch.n_heads // arch.n_kv_heads == 4 and arch.n_layers == 4
    w = REF.encoder_weights(params, jnp.float32)
    got = _served(arch, params, tokens)
    direct = np.asarray(forward_embed(params, {"tokens": jnp.asarray(tokens)},
                                      arch, ParallelConfig()))
    np.testing.assert_allclose(got, direct, atol=1e-6)
    dev = REF.deviation(cfg, w, tokens, got)
    assert 0.0 < dev <= LIMIT
    # the reference itself: unit rows, and a float32 pass in another
    # batch shape agrees with it far inside the limit
    want = np.asarray(REF.encode(cfg, w, tokens))
    np.testing.assert_allclose(np.linalg.norm(want, axis=-1), 1.0,
                               atol=1e-5)
    assert REF.deviation(cfg, w, tokens[:7], want[:7]) < LIMIT / 100


@pytest.mark.parametrize("fault", ["float8_weights", "one_layer_skipped"])
def test_planted_encoder_fault_reads_over_the_limit(encoder, fault):
    arch, cfg, params, tokens = encoder
    w = REF.encoder_weights(params, jnp.float32)
    if fault == "float8_weights":
        bad = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)
        got = _served(arch, bad, tokens)
    else:
        k = arch.n_layers - 1
        cut = dict(params, blocks=tuple(
            jax.tree_util.tree_map(lambda a: a[:k], b)
            for b in params["blocks"]))
        got = _served(dataclasses.replace(arch, n_layers=k, repeats=k),
                      cut, tokens)
    assert REF.deviation(cfg, w, tokens, got) > LIMIT


def test_altered_cache_answer_reads_not_correct(monkeypatch):
    """Every cache hit serves its rows' ids in reverse order: the same
    sets, so no range-search number moves, and the cache check sees it
    on the repeats of the window (all of its requests checked)."""
    get = ResultCache.get

    def reversed_hit(self, key):
        hit = get(self, key)
        if hit is None:
            return None
        ids, dists = hit
        return [a[::-1] for a in ids], [a[::-1] for a in dists]

    monkeypatch.setattr(ResultCache, "get", reversed_hit)
    cfg, traffic = small(CELL)
    out = harness.run_cell(CELL, SEED, 1.5, False, config=cfg,
                           traffic=dict(traffic, check_requests=512))
    checks = {k: v["value"] for k, v in out["checks"].items()}
    assert not out["correct"]
    assert checks["cache_mismatch_rows"] > 0
    assert checks["wrong_pairs"] == 0


def test_index_rows_in_bfloat16_read_not_correct(monkeypatch):
    """The index keeps the corpus's embeddings rounded to bfloat16 (in
    float32 arrays): the range search reads the rounded rows on both
    sides, and the stored-row check sees that they are not the
    encoder's."""
    embed_corpus = RetrievalService._embed_corpus

    def rounded(self, batches):
        e = embed_corpus(self, batches)
        return e.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(RetrievalService, "_embed_corpus", rounded)
    cfg, traffic = small(CELL)
    out = harness.run_cell(CELL, SEED, 0.3, False, config=cfg,
                           traffic=traffic)
    checks = {k: v["value"] for k, v in out["checks"].items()}
    assert not out["correct"]
    assert checks["stored_row_mismatch"] == cfg["stored_sample"]


def test_small_size_is_the_configuration_cut():
    """The CPU tests' size changes widths and scale only: the row kinds,
    the index settings and the guarantees are the configuration's."""
    spec = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    entry, = [c for c in spec["configs"] if c["name"] == "yi6b-served"]
    cfg = json.loads((harness.REPO / entry["file"]).read_text())
    over = json.loads((harness.BENCH / "configs"
                       / "yi6b-served.small.json").read_text())
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    kept = {"tables", "cap", "num_buckets", "hll_m", "beta_over_alpha",
            "lsh_delta", "delta_capacity", "template_frac", "family_frac",
            "doc_len", "guarantees", "metric", "precision"}
    assert not kept & set(over["config"])
    assert not {"row_kinds", "repeat_share", "loop"} & set(over["traffic"])
