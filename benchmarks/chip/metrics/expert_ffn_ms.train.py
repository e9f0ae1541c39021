"""Device self time per train step of the expert FFN, in the traced
window: ops of the step's module whose innermost layer scope is
``moe_experts`` (the grouped-GEMM kernels and their glue), all phases
together (``layers.layer_ms``)."""
from benchmarks.chip import layers


def read(ctx):
    return layers.total_ms(ctx, "moe_experts")
