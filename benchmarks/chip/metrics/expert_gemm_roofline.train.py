"""Roofline share of the grouped-GEMM expert kernels in training: the least
time of the forward and backward expert FFN work of every step in the
traced window (flops.grouped_gemm_fwd/bwd: B*S*k routed rows, every expert
used), over the summed device time of the ``grouped_gemm`` kernel ops,
recomputation under remat included."""
from benchmarks.chip import flops, trace


def read(ctx):
    if ctx["kind"] != "train" or not ctx["steps"]:
        return None
    d, t = ctx["dims"], ctx["traffic"]
    busy, ops = trace.kernel_ns(ctx["trace"], ("grouped_gemm",), ctx["lo"], ctx["hi"])
    if not ops:
        return None
    rows = t["batch"] * t["seq"] * d.top_k
    per_layer = flops.add(flops.grouped_gemm_fwd(rows, d.num_experts, d.d_model, d.d_ff),
                          flops.grouped_gemm_bwd(rows, d.num_experts, d.d_model, d.d_ff))
    work = flops.scale(per_layer, ctx["steps"] * d.num_layers)
    return 100.0 * flops.least_seconds(work, ctx["peaks"]) / (busy * 1e-9)
