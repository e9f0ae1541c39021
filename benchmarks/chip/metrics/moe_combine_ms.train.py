"""Device self time per train step of the MoE combine, in the traced
window: ops of the step's module whose innermost layer scope is
``moe_combine`` (the gather back to token order, the gate weighting, the
float32 scatter-add), all phases together (``layers.layer_ms``)."""
from benchmarks.chip import layers


def read(ctx):
    return layers.total_ms(ctx, "moe_combine")
