"""Roofline share of the flash-attention kernels in training: the least
time of the causal attention forward and backward of every step in the
traced window (flops.flash_fwd/bwd), over the summed device time of the
``flash_attention`` kernel ops, recomputation under remat included."""
from benchmarks.chip import flops, trace


def read(ctx):
    if ctx["kind"] != "train" or not ctx["steps"]:
        return None
    d, t = ctx["dims"], ctx["traffic"]
    busy, ops = trace.kernel_ns(ctx["trace"], ("flash_attention",), ctx["lo"], ctx["hi"])
    if not ops:
        return None
    args = (t["batch"], d.num_heads, d.num_kv_heads, t["seq"], d.head_dim)
    work = flops.scale(flops.add(flops.flash_fwd(*args), flops.flash_bwd(*args)),
                       ctx["steps"] * d.num_layers)
    return 100.0 * flops.least_seconds(work, ctx["peaks"]) / (busy * 1e-9)
