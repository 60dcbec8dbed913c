"""Encoder: least time the encoder's work could take on the chip, over
the device time of its program (``jit_forward_embed``) in the traced
window, in %.

The work is the algorithm's, over the rows not served from the cache:
each token passes every layer, 2 FLOPs per weight of the layer's
matmuls (the q, k, v and output projections and the gated MLP) plus
attention's 4 s d (scores and values over the document's s tokens);
each call reads the layers' bfloat16 weights once, and each token's
bfloat16 embedding row.  Padding rows, norms, softmax and pooling are
left out, so an encoder that does less than today's cannot read over
100%.
"""
import re

PROGRAM = re.compile(r"jit_forward_embed(\(.*)?")


def layer_weights(d, heads, kv_heads, head_dim, ffn):
    """Matmul weights of one layer."""
    return 2 * d * heads * head_dim + 2 * d * kv_heads * head_dim \
        + 3 * d * ffn


def work(calls, tokens, seq, layers, d, heads, kv_heads, head_dim, ffn):
    """(FLOPs, bytes) of ``calls`` encoder calls over ``tokens`` tokens
    in documents of ``seq``."""
    w = layer_weights(d, heads, kv_heads, head_dim, ffn)
    flops = tokens * layers * (2.0 * w + 4.0 * seq * d)
    nbytes = calls * layers * w * 2.0 + tokens * d * 2.0
    return flops, nbytes


def program_seconds(trace) -> float:
    """Device time of the encoder's program runs inside the window,
    averaged over the devices that ran it."""
    runs = [m for m in trace.modules if PROGRAM.fullmatch(m.name)
            and trace.lo <= m.start_ns < trace.hi]
    planes = {m.plane for m in runs}
    return sum(m.dur_ns for m in runs) * 1e-9 / max(len(planes), 1)


def read(ctx):
    w = ctx.work
    if ctx.trace is None or not w.get("encoder_tokens"):
        return None
    t = program_seconds(ctx.trace)
    if t <= 0:
        return None
    flops, nbytes = work(
        w["encoder_calls"], w["encoder_tokens"], w["encoder_seq"],
        w["encoder_layers"], w["dim"], w["encoder_heads"],
        w["encoder_kv_heads"], w["encoder_head_dim"], w["encoder_ffn"])
    tc, tm = flops / ctx.peaks["flops"], nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.log(f"encoder_roofline.served: {flops:.4g} FLOP, {nbytes:.4g} B, "
            f"bound by {'compute' if tc > tm else 'memory'}; program "
            f"{t:.6g} s")
    return 100.0 * max(tc, tm) / t
