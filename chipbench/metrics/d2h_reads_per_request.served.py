"""Blocking device-to-host reads per served request: the ``reads`` of
the program's ``repro.*.sync`` spans in the traced window
(``d2h_reads_per_request.index``'s), summed, over the window's
requests, cache hits included."""
from chipbench import harness

_SYNC = harness.load_module(harness.BENCH / "metrics"
                            / "d2h_reads_per_request.index.py").SYNC


def read(ctx):
    if ctx.trace is None or not ctx.served:
        return None
    reads = [e.args.get("reads", 0) for e in ctx.trace.program
             if e.name.endswith(_SYNC)]
    return sum(reads) / len(ctx.served) if reads else None
