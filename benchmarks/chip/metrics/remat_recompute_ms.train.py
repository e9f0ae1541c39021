"""Device self time per train step in recomputation for the backward pass,
in the traced window: ops of the step's module whose op-name path has
``rematted_computation``, the forward work that ``jax.checkpoint`` runs
again (``trace.phase``, reduced once by ``kinds/train.py:read_trace``).
Nothing where the configuration does not remat."""


def read(ctx):
    return ctx.get("phase_ms", {}).get("remat_recompute") or None
