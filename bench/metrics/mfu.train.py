"""Model FLOP utilization of training: forward and backward model FLOPs
of the window's tokens (attention included, recomputation not), over
window x chips x peak bf16 FLOP/s, in percent."""

import counts


def read(ctx):
    if ctx["kind"] != "train" or not ctx["steps"]:
        return None
    tr = ctx["traffic"]
    flops = (ctx["steps"] * ctx["tokens_per_step"]
             * counts.train_flops_per_token(ctx["dims"], tr["seq_len"]))
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["device"]["count"]
    return 100.0 * flops / (ctx["window_s"] * peak)
