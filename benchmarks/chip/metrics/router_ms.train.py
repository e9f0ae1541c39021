"""Device self time per train step of the MoE router, in the traced
window: ops of the step's module whose innermost layer scope is
``moe_router`` (router logits, softmax, top-k, auxiliary losses), all
phases together (``layers.layer_ms``)."""
from benchmarks.chip import layers


def read(ctx):
    return layers.total_ms(ctx, "moe_router")
