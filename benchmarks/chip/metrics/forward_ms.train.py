"""Device self time per train step in the forward pass, in the traced
window: ops of the step's module whose op-name path has ``jvp(`` and
neither ``transpose(`` nor ``rematted_computation`` (``trace.phase``,
reduced once by ``kinds/train.py:read_trace``)."""


def read(ctx):
    return ctx.get("phase_ms", {}).get("forward")
