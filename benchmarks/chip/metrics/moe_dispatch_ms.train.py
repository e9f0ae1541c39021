"""Device self time per train step of the MoE dispatch, in the traced
window: ops of the step's module whose innermost layer scope is
``moe_dispatch`` (the argsort, the index tables, the gather into the
expert-sorted buffer), all phases together (``layers.layer_ms``)."""
from benchmarks.chip import layers


def read(ctx):
    return layers.total_ms(ctx, "moe_dispatch")
