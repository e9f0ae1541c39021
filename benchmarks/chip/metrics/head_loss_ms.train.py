"""Device self time per train step of the LM head and the loss, in the
traced window: ops of the step's module whose innermost layer scope is
``head_loss`` (the final norm, the unembedding, the cross entropy), all
phases together (``layers.layer_ms``)."""
from benchmarks.chip import layers


def read(ctx):
    return layers.total_ms(ctx, "head_loss")
