"""The query-path kernels compile for a TPU v5e chip at deployment size.

Interpret mode (``tests/test_kernels.py``) proves the kernels' results;
it cannot show what the chip's compiler refuses: 1-D blocks, slices not
aligned to the (8, 128) tiling, more VMEM than a kernel may use.  These
tests compile each kernel through its ``ops`` wrapper for a described
(not attached) v5e chip, at the shapes ``chip_smoke.py`` runs:

* the linear route, one 32-query chunk of ``linear_search`` against the
  1,000,000 x 128 corpus (l2 and cosine);
* the LSH route against the same corpus, with L * cap = 20 * 128
  candidates per query, and at the served cell's 4096-dim width over
  its 131,072 documents;
* the HLL merge + estimate of a 256-query batch, L = 20, m = 64;
* result extraction's count and pack programs at the benchmark cells'
  group shapes: 16 linear rows of 1,097,729 slots and 16 LSH rows of
  24,065 packed into 2^23 pairs (mixed), and 32 LSH rows at the floor
  length (sparse).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a test worker that loads it keeps
it until it exits.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.kernels import ops

N, D = 1_000_000, 128          # chip_smoke.py index phase
L, CAP, M = 20, 128, 64
Q_CHUNK, Q_BATCH = 32, 256
N_SERVE, D_SERVE = 131_072, 4096  # yi6b-served's corpus (chipbench/configs/)
W_LINEAR, W_LSH = 1_097_729, 24_065  # group widths in the benchmark's cells


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _cases(one_chip):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def linear(metric):
        return (lambda q, x, r: ops.fused_linear_scan(
                    q, x, r, metric, impl="pallas"),
                (s((Q_CHUNK, D)), s((N, D)), s(())))

    def lsh(metric, n, d):
        return (lambda x, ids, q, r: ops.fused_lsh_scan(
                    x, ids, q, r, metric, impl="pallas"),
                (s((n, d)), s((Q_CHUNK, L * CAP), jnp.int32),
                 s((Q_CHUNK, d)), s(())))

    return {
        "linear_l2": linear("l2"),
        "linear_cosine": linear("cosine"),
        "lsh_l2": lsh("l2", N, D),
        "lsh_cosine_serving_width": lsh("cosine", N_SERVE, D_SERVE),
        "hll_merge_estimate": (
            lambda regs: ops.hll_merge_estimate(regs, impl="pallas"),
            (s((Q_BATCH, L, M), jnp.uint8),)),
    }


@pytest.mark.parametrize("case", ["linear_l2", "linear_cosine", "lsh_l2",
                                  "lsh_cosine_serving_width",
                                  "hll_merge_estimate"])
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, args = _cases(one_chip)[case]
    compiled = jax.jit(fn).lower(*args).compile()
    # the Pallas kernel itself, not an XLA stand-in, is in the program
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9, mem


EXTRACTION = {    # (rows, width, packed length) of each route group
    "mixed": [(16, W_LSH, None), (16, W_LINEAR, 1 << 23)],
    "sparse": [(32, W_LSH, None)],
}


@pytest.mark.parametrize("cell", sorted(EXTRACTION))
def test_extraction_compiles_for_v5e(cell, one_chip, no_persistent_cache):
    groups = EXTRACTION[cell]
    lengths = tuple(n or engine.PAIRS_FLOOR for _, _, n in groups)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bufs = [(s((g, w), jnp.int32), s((g, w), jnp.float32),
             s((g, w), jnp.bool_)) for g, w, _ in groups]
    idxs = [s((g,), jnp.int32) for g, _, _ in groups]
    firsts = [s((g,), jnp.bool_) for g, _, _ in groups]
    counts = engine._row_counts.lower(
        [b[2] for b in bufs], idxs, firsts,
        n_queries=sum(g for g, _, _ in groups)).compile()
    packed = engine._pack.lower(bufs, firsts, lengths=lengths,
                                impl="pallas").compile()
    assert "tpu_custom_call" in packed.as_text()    # the packing kernel
    for compiled in (counts, packed):
        assert compiled.memory_analysis().temp_size_in_bytes < 16e9
    assert [o.shape for pair in packed.out_info for o in pair] == \
        [(n,) for n in lengths for _ in range(2)]
