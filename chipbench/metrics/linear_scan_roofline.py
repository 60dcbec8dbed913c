"""Linear-route kernel: least time the r-NN work could take on the chip,
over the kernel's device time in the trace, in %.

The work is the algorithm's, from shapes and counts the benchmark makes:
each request's linear rows are compared with every live row, reading
the live rows and the queries once and writing each reported pair (id
and distance).  No tile, pad, output buffer or re-read enters it, so a
kernel that does less than today's cannot read over 100%.  The kernel
time also holds the delta's scans for LSH-routed rows (the delta is
searched with the same kernel), which this work leaves out.
"""

# the kernel's ops as a v5e trace names them (short HLO name)
KERNELS = (r"%linear_scan_dot_pallas(\.\d+)?",)


def work(calls: float, rows: float, live_rows: float, dim: float,
         reported_pairs: float):
    """(FLOPs, bytes): ``calls`` linear searches holding ``rows`` query
    rows in all, over ``live_rows`` rows of ``dim`` float32."""
    flops = 2.0 * rows * live_rows * dim
    nbytes = calls * live_rows * dim * 4 + rows * dim * 4 + reported_pairs * 8
    return flops, nbytes


def read(ctx):
    w = ctx.work
    if ctx.trace is None or not w.get("linear_rows"):
        return None
    t = ctx.trace.kernel_seconds(KERNELS)
    if t <= 0:
        return None
    flops, nbytes = work(w["linear_calls"], w["linear_rows"],
                         w["live_rows"], w["dim"], w["linear_pairs"])
    tc, tm = flops / ctx.peaks["flops"], nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.log(f"linear_scan_roofline: {flops:.4g} FLOP, {nbytes:.4g} B, "
            f"bound by {'compute' if tc > tm else 'memory'}; kernel {t:.6g} s")
    return 100.0 * max(tc, tm) / t
