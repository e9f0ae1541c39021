"""Device self time per train step in the backward pass, in the traced
window: ops of the step's module whose op-name path has ``transpose(``
and not ``rematted_computation``; recomputation is counted apart
(``trace.phase``, reduced once by ``kinds/train.py:read_trace``)."""


def read(ctx):
    return ctx.get("phase_ms", {}).get("backward")
