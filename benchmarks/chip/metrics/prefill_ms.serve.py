"""Device self time per run of the engine's prefill-chunk program in the
traced window: the summed self time of the ops inside its runs over the
number of runs (``serve_trace.programs``; ``kinds/serve.py:read_trace``)."""


def read(ctx):
    p = ctx.get("programs", {}).get("prefill")
    if not p or not p["runs"]:
        return None
    return p["self_ns"] * 1e-6 / p["runs"]
