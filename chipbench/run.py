"""Run one cell of the chip benchmark once, on the machine's TPU.

    python3 chipbench/run.py --workload hybridlsh-densecore-l2.mixed --seed 7 \
        --seconds 20 --trace 0

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number the reference compared,
beside its limit (also the last lines of stderr).  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()   # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def require_chips(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform!r} devices; "
                         "the benchmark runs only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    from repro.kernels import ops
    if ops.resolve_impl() != "pallas":
        raise SystemExit("kernels do not dispatch to compiled Pallas")


def enable_cache() -> None:
    """JAX's persistent compilation cache, in the checkout."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    from chipbench import harness
    harness.log(f"compile cache: {enable_compile_cache()}")
    # every program, however quick to compile, is kept: only a cell's
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import harness
    cell = harness.Cell(args.workload)
    require_chips(int(cell.workload["chips"]))

    enable_cache()

    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    for name, c in out["checks"].items():
        harness.log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
