"""Faults planted under the timed path, to show that ``correct`` sees them.

    python3 chipbench/faults.py --workload hybridlsh-densecore-l2.mixed \
        --fault lsh_half --seeds 1 2 3 --seconds 5

Each fault replaces the program's ``QueryResult.reported``, the call that
brings a row's answer to the caller, with a broken one.  For each seed
the cell then runs as ``run.py`` runs it (set-up, window, reference), and
the numbers compared are printed beside their limits.  The benchmark's
runs never plant a fault.
"""
import argparse
import contextlib
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def _half_left_out(orig):
    """The second half of every request's rows gets no answer."""
    def reported(self, i):
        ids, dists = orig(self, i)
        if i >= self.n_queries // 2:
            return ids[:0], dists[:0]
        return ids, dists
    return reported


def _answer_altered(orig):
    """One id of every row's answer is replaced by the next id."""
    def reported(self, i):
        ids, dists = orig(self, i)
        if len(ids):
            ids = ids.copy()
            ids[0] = ids[0] + 1
        return ids, dists
    return reported


def _lsh_half(orig):
    """Rows the LSH route served lose every other candidate it found."""
    def reported(self, i):
        ids, dists = orig(self, i)
        if i in set(int(j) for j in self.lsh_idx):
            return ids[::2], dists[::2]
        return ids, dists
    return reported


FAULTS = {"half_left_out": _half_left_out,
          "answer_altered": _answer_altered,
          "lsh_half": _lsh_half}


@contextlib.contextmanager
def planted(name: str):
    """The fault ``name`` in place for the duration."""
    from repro.core.engine import QueryResult

    orig = QueryResult.reported
    QueryResult.reported = FAULTS[name](orig)
    try:
        yield
    finally:
        QueryResult.reported = orig


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()

    from chipbench import harness, run

    cell = harness.Cell(args.workload)
    run.require_chips(int(cell.workload["chips"]))
    run.enable_cache()
    for seed in args.seeds:
        with planted(args.fault):
            out = harness.run_cell(args.workload, seed, args.seconds, False)
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)


if __name__ == "__main__":
    main()
