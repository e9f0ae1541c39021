"""Model FLOP utilisation of serving: the model FLOPs of every prefill
chunk and decode step that the engine ran in the traced window
(``flops_serve``: real tokens only, attention over each token's real
context, the LM head where a logit is used) / (window x chips x peak bf16
FLOP/s)."""
from benchmarks.chip import flops_serve


def read(ctx):
    if ctx["kind"] != "serve" or not (ctx["chunks"] or ctx["decodes"]):
        return None
    window_s = (ctx["hi"] - ctx["lo"]) * 1e-9
    return 100.0 * flops_serve.window_flops(ctx) / (
        window_s * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
