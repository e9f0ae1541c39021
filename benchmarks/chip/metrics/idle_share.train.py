"""Share of the traced window in which no op ran on the device (the union
of the ``XLA Ops`` intervals), averaged over the cell's chips."""
from benchmarks.chip import trace


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    if hi <= lo:
        return None
    return 100.0 * (1.0 - trace.mean_busy_ns(ctx["trace"], lo, hi) / (hi - lo))
