"""Model FLOP utilisation of training: model FLOPs per token from shapes
(flops.train_flops_per_token: forward + backward, no recompute, no
embedding lookup) x tokens trained in the traced window / (window x chips x
peak bf16 FLOP/s)."""
from benchmarks.chip import flops


def read(ctx):
    if ctx["kind"] != "train" or not ctx["tokens"]:
        return None
    window_s = (ctx["hi"] - ctx["lo"]) * 1e-9
    work = ctx["tokens"] * flops.train_flops_per_token(ctx["dims"], ctx["traffic"]["seq"])
    return 100.0 * work / (window_s * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
