"""Multi-device tests (subprocess with 8 host platform devices):
  * sharded Hybrid LSH index == single-host results (collisions,
    candSize estimate, reported neighbors);
  * per-shard routing under skew;
  * sharded train step runs under the debug mesh and matches the
    unsharded loss;
  * int8 EF compressed psum == plain psum within quantization error.
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


def test_sharded_index_matches_single_host():
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.core import CostModel, HybridLSHIndex
from repro.core.distributed import build_sharded, make_query_fn
from repro.core.lsh import make_family
from repro.data import clustered_dataset, query_split

assert len(jax.devices()) == 8
mesh = jax.make_mesh((8,), ("data",))
n, d, r = 4096, 16, 0.5
x = clustered_dataset(n + 64, d, n_clusters=8, dense_core_frac=0.2,
                      seed=0)
x, q = query_split(x, 64, seed=0)
x = x[:n]
fam = make_family("l2", d=d, L=40, r=r)
params = fam.init(jax.random.PRNGKey(0))
bound = 1.0 - (1.0 - fam.p1(r) ** fam.k) ** fam.L
cm = CostModel(1.0, 10.0)

state = build_sharded(fam, params, jnp.asarray(x), num_buckets=512,
                      m=32, mesh=mesh)
qfn = make_query_fn(fam, num_buckets=512, mesh=mesh, n_total=n,
                    cost_model=cm, metric="l2", cap=256, max_out=512,
                    policy="per_shard")
res = qfn(state, params, jnp.asarray(q), r)

# exact collision count check vs single-host index with same params
idx = HybridLSHIndex(fam, num_buckets=512, m=32, cap=256,
                     cost_model=cm, key=0)
idx.params = params
idx.build(jnp.asarray(x))
est = idx.estimate(jnp.asarray(q))

# NOTE: per-shard tables hash the same points with the same g_j, so
# summed collision counts must agree exactly.
np.testing.assert_array_equal(np.asarray(res["collisions"]),
                              np.asarray(est.collisions))

# distributed (pmax-merged) candSize estimate == single-host estimate
np.testing.assert_allclose(np.asarray(res["cand_est"]),
                           np.asarray(est.cand_est), rtol=1e-5)

# reported neighbor sets == brute force
D = np.sqrt(((q[:, None] - x[None]) ** 2).sum(-1))
ids = np.asarray(res["ids"]).reshape(-1, len(q), 512)
mask = np.asarray(res["mask"]).reshape(-1, len(q), 512)
miss = 0
total = 0
for i in range(len(q)):
    got = set()
    for s_ in range(ids.shape[0]):
        got |= set(ids[s_, i][mask[s_, i]].tolist())
    gt = set(np.nonzero(D[i] <= r)[0].tolist())
    assert got <= gt, "false positives"
    total += len(gt)
    miss += len(gt - got)
print("RECALL", (1.0 - miss / max(total, 1)) / bound)
print("USED_LSH", np.asarray(res["used_lsh"]).tolist())
""")
    # recall is normalized by the worst-case theory bound
    # 1-(1-p1(r)^k)^L in the subprocess script
    recall = float(out.split("RECALL")[1].split()[0])
    assert recall >= 0.8, out


def test_sharded_train_step_matches_single_device():
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, reduced_config
from repro.launch.mesh import make_debug_mesh
from repro.models.parallel import ParallelConfig
from repro.train.step import TrainConfig, init_state, make_jitted_train_step
from repro.data import lm_batch

cfg = reduced_config(get_config("yi-6b"))
mesh = make_debug_mesh((4, 2), ("data", "model"))
par_sh = ParallelConfig(mesh=mesh, data_axes=("data",), seq_shard=True,
                        attn_chunk_q=8, attn_chunk_k=8, logits_chunk=8)
par_1 = ParallelConfig(mesh=None, attn_chunk_q=8, attn_chunk_k=8,
                       logits_chunk=8)
tcfg = TrainConfig(total_steps=10, warmup_steps=0)
# two independent states: the jitted steps DONATE their input state
state_a = init_state(cfg, jax.random.PRNGKey(0), tcfg)
state_b = init_state(cfg, jax.random.PRNGKey(0), tcfg)
batch = lm_batch(0, 0, batch=8, seq=16, vocab=cfg.vocab, cfg=cfg)

s1, m1 = make_jitted_train_step(cfg, par_1, tcfg)(state_a, batch)
s2, m2 = make_jitted_train_step(cfg, par_sh, tcfg)(state_b, batch)
print("LOSS1", float(m1["loss"]), "LOSS2", float(m2["loss"]))
d = jax.tree_util.tree_map(
    lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32)))),
    s1["params"], s2["params"])
print("MAXDIFF", max(jax.tree_util.tree_leaves(d)))
""")
    l1 = float(out.split("LOSS1")[1].split()[0])
    l2 = float(out.split("LOSS2")[1].split()[0])
    assert abs(l1 - l2) < 5e-2 * max(1.0, abs(l1)), out
    maxdiff = float(out.split("MAXDIFF")[1].split()[0])
    assert maxdiff < 0.05, out


def test_compressed_psum_matches_plain():
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.optim.compression import compressed_psum

mesh = jax.make_mesh((8,), ("pod",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 1024)) * 0.01

def body(xs):
    return compressed_psum(xs[0], "pod", 8)

fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("pod"),
                       out_specs=P(None), check_vma=False))
got = np.asarray(fn(x))
want = np.asarray(x.mean(0))
err = np.abs(got - want).max() / (np.abs(want).max() + 1e-12)
print("RELERR", err)
""")
    err = float(out.split("RELERR")[1].split()[0])
    assert err < 0.02, out


def test_flash_decode_seq_sharded_matches_plain():
    out = _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.models.attention import flash_decode
from repro.models.parallel import ParallelConfig

mesh = jax.make_mesh((2, 4), ("data", "model"))
par = ParallelConfig(mesh=mesh, data_axes=("data",),
                     decode_seq_shard=("model",))
b, h, hkv, hd, s = 4, 8, 2, 16, 64
k = jax.random.PRNGKey(0)
q = jax.random.normal(k, (b, h, hd))
kc = jax.random.normal(k, (b, s, hkv, hd))
vc = jax.random.normal(k, (b, s, hkv, hd))
lengths = jnp.array([64, 50, 33, 7], jnp.int32)

plain = flash_decode(q, kc, vc, lengths, None, seq_axes=())
shard = jax.jit(lambda *a: flash_decode(*a, par, seq_axes=("model",)))(
    q, kc, vc, lengths)
np.testing.assert_allclose(np.asarray(plain), np.asarray(shard),
                           rtol=2e-5, atol=2e-5)
print("FLASH_OK")
""")
    assert "FLASH_OK" in out
