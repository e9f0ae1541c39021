"""Roofline share of the grouped-GEMM expert kernels in serving: the least
time of the expert FFN of every prefill chunk and decode step in the
traced window, call by call and layer by layer
(``flops_serve.expert_gemm`` over the call's real tokens), over the summed
device time of the ``grouped_gemm`` kernel ops."""
from benchmarks.chip import flops_serve, trace


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    busy, ops = trace.kernel_ns(ctx["trace"], ("grouped_gemm",), ctx["lo"], ctx["hi"])
    if not ops:
        return None
    d = ctx["dims"]
    tokens = [n for _, n, _ in ctx["chunks"]] + [len(lens) for lens in ctx["decodes"]]
    least = flops_serve.least_seconds(ctx, (flops_serve.expert_gemm(d, n) for n in tokens if n))
    return 100.0 * least / (busy * 1e-9)
