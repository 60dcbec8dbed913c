"""Plain reference for ``hybridlsh-densecore-l2``: float32 brute-force
range search.

Distances are direct differences ``sum((x - q)^2)`` in float32 over every
row, with no index, no kernel and nothing the program made: the corpus
and the deletions are drawn again from the configuration
(``chipbench.clustered``), the queries are the benchmark's own.
Nothing here imports the program.

``check`` compares the answers of a sample of the window's requests,
row by row, against the reference and returns two numbers, held to
``LIMITS``:

``wrong_pairs``
  * a reported id that is not a live row within r, a deleted id, an id
    out of range, or an id reported twice in a row;
  * in a row the linear route served, a live row within r not reported;
  * in a row the LSH route served that has at most
    ``deep_max_reference`` rows within r, a row within ``deep_frac * r``
    not reported (a row that close collides in some table with near
    certainty).

``lsh_recall_shortfall``
  The LSH route reports a subset by design.  Each of its rows with at
  most ``deep_max_reference`` rows within r (so cap truncation cannot
  bind) should find ``ceil(g W)`` of its ``W`` rows within r, ``g`` the
  recall the configured family gives a row at distance exactly r (every
  row within r collides at least that often).  The number is the rows
  found short of that, summed over the rows, as a share of their
  ``W``s.

Rows within float32 reach of r (the band ``|d^2 - r^2| <= 1e-5 (|q|^2 +
|x|^2)``) are accepted either way and left out of ``W``.

``control`` is the same reference with bfloat16 differences and squares
(the precision below the configuration's float32) in the rows it is
told to lower, put in the program's place: its answers must fail
``check``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import clustered

LIMITS = {"wrong_pairs": 0, "lsh_recall_shortfall": 0.08}
BAND = 1e-5
OUTSIDE, INSIDE, IN_BAND, DEEP, DEAD = 0, 1, 2, 3, 4


def pstable_collision(t: float) -> float:
    """Collision probability of one p-stable L2 hash of bucket width w
    for two points at distance c, with t = w / c (Datar et al. 2004)."""
    tail = 0.5 * math.erfc(t / math.sqrt(2.0))
    return (1.0 - 2.0 * tail
            - 2.0 / (math.sqrt(2.0 * math.pi) * t)
            * (1.0 - math.exp(-t * t / 2.0)))


def recall_at_r(cfg: Dict) -> float:
    """The share of rows at distance r that ``tables`` tables of
    ``lsh_k`` concatenated hashes of width ``lsh_w_over_r * r`` find."""
    p = pstable_collision(float(cfg["lsh_w_over_r"])) ** int(cfg["lsh_k"])
    return 1.0 - (1.0 - p) ** int(cfg["tables"])


@jax.jit
def _codes(q, x, alive, r2, deep2):
    """(k, n) int8: each row's class per corpus row (see the constants)."""
    d2 = jax.lax.map(lambda qi: jnp.sum(jnp.square(x - qi), axis=-1), q)
    tol = BAND * (jnp.sum(q * q, -1)[:, None] + jnp.sum(x * x, -1)[None, :])
    code = jnp.where(d2 <= r2, INSIDE, OUTSIDE)
    code = jnp.where(d2 <= deep2, DEEP, code)
    code = jnp.where(jnp.abs(d2 - r2) <= tol, IN_BAND, code)
    return jnp.where(alive[None, :], code, DEAD).astype(jnp.int8)


@jax.jit
def _control_mask(q, x, alive, r2, low):
    """Rows with ``low`` set: bfloat16 differences and squares, float32
    sums; the others: float32 throughout."""
    xb = x.astype(jnp.bfloat16)

    def one(args):
        qi, lo = args
        sq = jnp.square(xb - qi.astype(jnp.bfloat16)).astype(jnp.float32)
        d2 = jnp.where(lo, jnp.sum(sq, axis=-1),
                       jnp.sum(jnp.square(x - qi), axis=-1))
        return d2

    return (jax.lax.map(one, (q, low)) <= r2) & alive[None, :]


class Reference:
    """The configuration's corpus and deletions, on the device."""

    def __init__(self, cfg: Dict, r: float):
        self.cfg = cfg
        self.x = clustered.corpus(cfg).x
        self.alive = jnp.asarray(clustered.alive_mask(cfg))
        self.r = float(r)

    def codes(self, q: np.ndarray) -> np.ndarray:
        deep = float(self.cfg["deep_frac"]) * self.r
        return np.asarray(_codes(jnp.asarray(q, jnp.float32), self.x,
                                 self.alive, jnp.float32(self.r ** 2),
                                 jnp.float32(deep ** 2)))

    def control(self, q: np.ndarray,
                low: Optional[np.ndarray] = None) -> List[np.ndarray]:
        """Each row's ids within r, in bfloat16 where ``low`` (default:
        every row)."""
        low = np.ones(q.shape[0], bool) if low is None else np.asarray(low)
        m = np.asarray(_control_mask(jnp.asarray(q, jnp.float32), self.x,
                                     self.alive, jnp.float32(self.r ** 2),
                                     jnp.asarray(low, bool)))
        return [np.nonzero(row)[0] for row in m]


def compare_row(code: np.ndarray, ids: np.ndarray, linear: bool,
                deep_max: int, recall: float) -> Dict[str, int]:
    """Counts of one row's answer ``ids`` against its reference ``code``."""
    ids = np.asarray(ids, np.int64)
    n = code.shape[0]
    ok = (ids >= 0) & (ids < n)
    uniq = np.unique(ids[ok])
    got = code[uniq]
    inside = (code == INSIDE) | (code == DEEP)
    found = int(((got == INSIDE) | (got == DEEP)).sum())
    out = {
        "reported": int(len(ids)),
        "reference": int(inside.sum()),
        "band": int((code == IN_BAND).sum()),
        "bad_id": int((~ok).sum()),
        "duplicate": int(ok.sum() - len(uniq)),
        "outside_or_dead": int(((got == OUTSIDE) | (got == DEAD)).sum()),
        "missed_linear": 0,
        "missed_deep": 0,
        "lsh_within": 0,
        "lsh_found": 0,
        "lsh_short": 0,
    }
    if linear:
        out["missed_linear"] = out["reference"] - found
    elif out["reference"] <= deep_max:
        out["missed_deep"] = int((code == DEEP).sum()) - int(
            (got == DEEP).sum())
        out["lsh_within"] = out["reference"]
        out["lsh_found"] = found
        out["lsh_short"] = max(
            0, math.ceil(recall * out["reference"] - 1e-9) - found)
    return out


WRONG = ("bad_id", "duplicate", "outside_or_dead", "missed_linear",
         "missed_deep")


def check(ref: Reference,
          sample: Sequence[Tuple[np.ndarray, List[np.ndarray], np.ndarray]]
          ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """``sample``: (query rows, reported ids per row, linear-route flag
    per row) per checked request.  Returns (numbers compared, tallies)."""
    tally: Dict[str, int] = {}
    deep_max = int(ref.cfg["deep_max_reference"])
    recall = recall_at_r(ref.cfg)
    for q, ids, linear in sample:
        codes = ref.codes(q)
        for j in range(q.shape[0]):
            for k, v in compare_row(codes[j], ids[j], bool(linear[j]),
                                    deep_max, recall).items():
                tally[k] = tally.get(k, 0) + v
    tally["rows"] = int(sum(q.shape[0] for q, _, _ in sample))
    within = tally.get("lsh_within", 0)
    return {"wrong_pairs": float(sum(tally.get(k, 0) for k in WRONG)),
            "lsh_recall_shortfall": (tally.get("lsh_short", 0) / within
                                     if within else 0.0)}, tally
