"""Roofline share of the paged-attention decode kernel: the least time of
every decode step's attention in the traced window, layer by layer
(``flops_serve.paged_attention``: the K and V pages of each live row's
context, its q and out), over the summed device time of the
``paged_attention`` kernel ops."""
from benchmarks.chip import flops_serve, trace


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    busy, ops = trace.kernel_ns(ctx["trace"], ("paged_attention",), ctx["lo"], ctx["hi"])
    if not ops:
        return None
    d, ps = ctx["dims"], ctx["page_size"]
    least = flops_serve.least_seconds(
        ctx, (flops_serve.paged_attention(d, lens, ps) for lens in ctx["decodes"] if lens))
    return 100.0 * least / (busy * 1e-9)
