"""Model FLOP utilization of serving: forward FLOPs of the rows that
served a request in the window's pool steps (each at its own context
length), over window x peak bf16 FLOP/s, in percent."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["window_iterations"]:
        return None
    return 100.0 * ctx["flops"] / (ctx["window_s"]
                                   * ctx["peaks"]["bf16_flops_per_s"])
