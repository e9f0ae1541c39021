"""Device self time per train step of the trainer's anomaly guard, in the
traced window: ops of the step's module whose innermost layer scope is
``anomaly_guard`` (the gradient scale, the skip predicate, and the
``jnp.where`` that selects the whole parameter and optimizer state back)
(``layers.layer_ms``). A fused op carries the path of its root: where XLA
fuses AdamW's elementwise update into the guard's select, as on the TPU
v5e, that fusion is counted here and ``adamw`` reads nothing."""
from benchmarks.chip import layers


def read(ctx):
    return layers.total_ms(ctx, "anomaly_guard")
