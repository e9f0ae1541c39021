"""Device self time per train step of the attention layer, in the traced
window: ops of the step's module whose innermost layer scope is
``attention`` (QKV and out projections, rope, the flash-attention kernel),
in the forward pass, its recomputation and the backward pass together
(``layers.layer_ms``)."""
from benchmarks.chip import layers


def read(ctx):
    return layers.total_ms(ctx, "attention")
