"""The one traffic generator: a traffic mix's parameters -> a request plan.

A mix is a JSON file under ``chipbench/traffic/`` named by the cell's
``traffic``.  Its keys:

* ``loop``: the loop that drives the window, a module under
  ``chipbench/loops/`` (``closed``: callers send back to back);
* ``callers``: how many callers the loop runs;
* ``rows_per_request``: query rows in each request;
* ``row_kinds``: ``{kind: share}``; every request holds that share of
  its rows of each kind (rounded), in an order drawn from the seed.  A
  kind names a way of drawing a query row that the configuration's
  system adapter knows (``dense``, ``sparse``, ...);
* ``pool_requests``: distinct requests made from the seed; the window
  cycles through them in order;
* ``check_requests``: requests of the window, drawn from the seed, whose
  answers the reference checks.

Beyond these six, a mix holds exactly the keys its loop declares
(``KEYS`` in ``loops/<loop>.py``: a rate, bursts, ...), and the plan
hands them to the loop (``Plan.loop_params``).  A key missing or any
other key is an error.

Every seed gets the same sizes and the same count of each kind in every
request; only the draws and their order differ, so seeds do not change
the work.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List

import numpy as np

LOOPS = Path(__file__).resolve().parent / "loops"
_KEYS = {"loop", "callers", "rows_per_request", "row_kinds",
         "pool_requests", "check_requests"}


@dataclasses.dataclass(frozen=True)
class Plan:
    loop: str
    callers: int
    kind_names: List[str]
    kinds: np.ndarray          # (pool_requests, rows_per_request) int8
    check_requests: int
    loop_params: Dict          # the mix's keys its loop declares

    @property
    def pool(self) -> int:
        return self.kinds.shape[0]

    def count(self, kind: str) -> int:
        """Rows of ``kind`` over the whole pool."""
        if kind not in self.kind_names:
            return 0
        return int(np.sum(self.kinds == self.kind_names.index(kind)))


def plan(traffic: Dict, seed: int) -> Plan:
    from chipbench import harness   # harness imports this module

    if "loop" not in traffic:
        raise ValueError("traffic keys: missing ['loop']")
    own = set(harness.load_module(LOOPS / f"{traffic['loop']}.py").KEYS)
    unknown = set(traffic) - _KEYS - own
    missing = (_KEYS | own) - set(traffic)
    if unknown or missing:
        raise ValueError(f"traffic keys: unknown {sorted(unknown)}, "
                         f"missing {sorted(missing)}")
    names = sorted(traffic["row_kinds"])
    shares = np.array([float(traffic["row_kinds"][k]) for k in names])
    if np.any(shares < 0) or not np.isclose(shares.sum(), 1.0):
        raise ValueError(f"row_kinds shares must sum to 1: {shares}")
    rows = int(traffic["rows_per_request"])
    counts = np.floor(shares * rows + 0.5).astype(int)
    if counts.sum() != rows:
        raise ValueError(f"row_kinds shares do not split {rows} rows")
    one = np.repeat(np.arange(len(names)), counts)
    rng = np.random.default_rng([int(seed), 3])
    kinds = np.stack([rng.permutation(one)
                      for _ in range(int(traffic["pool_requests"]))])
    return Plan(loop=str(traffic["loop"]), callers=int(traffic["callers"]),
                kind_names=names, kinds=kinds.astype(np.int8),
                check_requests=int(traffic["check_requests"]),
                loop_params={k: traffic[k] for k in sorted(own)})
