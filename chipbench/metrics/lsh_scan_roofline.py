"""LSH-route kernel: least time the r-NN work could take on the chip,
over the kernel's device time in the trace, in %.

The work is the algorithm's: each distinct candidate (the first ``cap``
ids of every probed bucket, in every frozen segment) is read once as a
float32 row plus its id, and compared with its query; each query row is
read once.  No slab, tile, pad or empty slot enters it, so a kernel
that moves less than today's cannot read over 100%.
"""

# the kernel's ops as a v5e trace names them (short HLO name)
KERNELS = (r"%lsh_scan_pallas(\.\d+)?",)


def work(candidates: float, rows: float, dim: float):
    """(FLOPs, bytes) of verifying ``candidates`` distinct candidates of
    ``rows`` query rows, ``dim`` float32 each."""
    flops = 2.0 * candidates * dim
    nbytes = candidates * (dim * 4 + 4) + rows * dim * 4
    return flops, nbytes


def read(ctx):
    w = ctx.work
    if ctx.trace is None or not w.get("lsh_candidates"):
        return None
    t = ctx.trace.kernel_seconds(KERNELS)
    if t <= 0:
        return None
    flops, nbytes = work(w["lsh_candidates"], w["lsh_rows"], w["dim"])
    tc, tm = flops / ctx.peaks["flops"], nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.log(f"lsh_scan_roofline: {flops:.4g} FLOP, {nbytes:.4g} B, "
            f"bound by {'compute' if tc > tm else 'memory'}; kernel {t:.6g} s")
    return 100.0 * max(tc, tm) / t
