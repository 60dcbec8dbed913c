"""The control of a cell's ``correct``: the reference in bfloat16 in the
program's place, at the cell's own size, compared as a run compares.

    python3 chipbench/control.py --workload hybridlsh-densecore-l2.mixed \
        --seeds 1 2 3 [--rows linear]

For each seed it draws the cell's inputs with its system adapter's
``inputs``, takes as many requests of the pool as a run checks, answers
them with the configuration reference's ``control`` (each row labelled
linear or LSH route as the adapter's ``linear`` says), and prints the
numbers ``check`` compares beside their limits.  ``--rows linear``
lowers only the rows labelled linear and answers the others in float32.
A sound comparison reads every control as not correct.  The benchmark's
runs never run it.
"""
import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def control_readings(workload: str, seed: int, config=None, traffic=None,
                     rows: str = "all"):
    """(numbers, tallies) of the control for one seed, in bfloat16 in
    ``rows`` (``all`` or ``linear``); ``config`` and ``traffic`` replace
    the cell's own (tests use a small size)."""
    import numpy as np

    from chipbench import generator, harness

    cell = harness.Cell(workload)
    cfg = config if config is not None else cell.config
    plan = generator.plan(traffic if traffic is not None else cell.traffic,
                          seed)
    ref_mod = harness.load_module(cell.reference_path)
    inp = harness.load_module(cell.system_path).inputs(seed, cfg, plan)
    rng = np.random.default_rng([int(seed), 6])
    pick = rng.choice(plan.pool, size=min(plan.check_requests, plan.pool),
                      replace=False)
    ref = ref_mod.Reference(cfg, inp.r)
    sample = []
    for i in sorted(pick):
        linear = inp.linear[i]
        low = linear if rows == "linear" else np.ones_like(linear)
        sample.append((inp.requests[i], ref.control(inp.requests[i], low),
                       linear))
    return ref_mod.check(ref, sample)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", choices=("all", "linear"), default="all")
    args = ap.parse_args()
    from chipbench import harness
    for seed in args.seeds:
        numbers, tally = control_readings(args.workload, seed,
                                          rows=args.rows)
        harness.log(f"seed {seed}: {json.dumps(tally, sort_keys=True)}")
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "rows": args.rows, "numbers": numbers}),
              flush=True)


if __name__ == "__main__":
    main()
