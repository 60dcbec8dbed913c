"""The query-path kernels compile for a TPU v5e chip at deployment size.

Interpret mode (``tests/test_kernels.py``) proves the kernels' results;
it cannot show what the chip's compiler refuses: 1-D blocks, slices not
aligned to the (8, 128) tiling, more VMEM than a kernel may use.  These
tests compile each kernel through its ``ops`` wrapper for a described
(not attached) v5e chip, at the shapes ``chip_smoke.py`` runs:

* the linear route, one 32-query chunk of ``linear_search`` against the
  1,000,000 x 128 corpus (l2 and cosine);
* the LSH route against the same corpus, with L * cap = 20 * 128
  candidates per query, and at the serving phase's 4096-dim width;
* the HLL merge + estimate of a 256-query batch, L = 20, m = 64.

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

from repro.kernels import ops

N, D = 1_000_000, 128          # chip_smoke.py index phase
L, CAP, M = 20, 128, 64
Q_CHUNK, Q_BATCH = 32, 256
N_SERVE, D_SERVE = 65_536, 4096  # chip_smoke.py serving phase


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
