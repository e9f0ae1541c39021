"""Device self time per train step outside the gradient, in the traced
window: ops of the step's module whose op-name path has none of ``jvp(``,
``transpose(``, ``rematted_computation``: AdamW, the gradient norm and
clip, the anomaly guard, the schedule and the RNG split (``trace.phase``,
reduced once by ``kinds/train.py:read_trace``)."""


def read(ctx):
    return ctx.get("phase_ms", {}).get("optimizer_update")
