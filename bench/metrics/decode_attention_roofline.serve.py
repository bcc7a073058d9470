"""Roofline share of the Pallas ``decode_attention`` calls: the least
time the valid K/V bytes of the rows serving a request need at peak
HBM bandwidth (memory-bound), over the calls' device time in the trace,
in percent. One call per layer per pool step; its bound is the mean
over the window's steps."""

import counts
import tracing


def read(ctx):
    if ctx["kind"] != "serve" or ctx["trace"] is None or \
            not ctx["attn_calls"]:
        return None
    hit = tracing.kernel_calls(ctx["trace"],
                               tracing.KERNELS["decode_attention"])
    if hit is None or hit[1] <= 0:
        return None
    calls, seconds = hit
    bounds = [counts.roofline_seconds(
        counts.decode_attention_call(ctx["dims"], lengths),
        ctx["peaks"])[0] for lengths in ctx["attn_calls"]]
    return 100.0 * calls * (sum(bounds) / len(bounds)) / seconds
