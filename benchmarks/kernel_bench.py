"""Kernel microbench: Pallas expert_gemm / grouped_gemm / flash_attention vs
their XLA reference paths, forward AND backward, plus the padded-vs-sorted
dropless dispatcher comparison.

On this CPU container the Pallas kernels run in interpret mode (Python), so
kernel wall-times are NOT hardware-representative; we therefore report
(a) XLA-path fwd and fwd+bwd wall time as the throughput baseline,
(b) kernel-vs-ref max error (fwd and grad), and (c) derived activation /
HBM-traffic accounting — the quantities the kernels exist to optimize on
TPU. The quant rows pair bf16 against int8 on both fused-dequant paths
(grouped GEMM and paged attention) and gate the ``bytes_per_row``
reduction at >= 1.8x. The backward rows carry the recompute accounting: the custom_vjp saves
only O(N*D) residuals, so ``residual_bytes`` (measured from the actual VJP
residual pytree) vs ``xla_saved_bytes`` (the (N,F) gate/up/h intermediates
autodiff would keep) is the per-layer activation-memory win, asserted here
so a regression that starts saving an (N, F) residual fails the bench.

Output: CSV on stdout, JSON via benchmarks.common.emit, and a
machine-readable ``BENCH_kernels.json`` at the repo root so the perf
trajectory is tracked across PRs.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.kernels.expert_gemm import grouped_gemm_residuals
from repro.kernels.ops import (
    expert_gemm,
    flash_attention,
    grouped_gemm,
    grouped_gemm_xla,
)
from repro.kernels.ref import expert_gemm_ref, flash_attention_ref

ROOT_JSON = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "BENCH_kernels.json")


def _grad_err(loss_a, loss_b, args):
    ga = jax.grad(loss_a, argnums=tuple(range(len(args))))(*args)
    gb = jax.grad(loss_b, argnums=tuple(range(len(args))))(*args)
    return max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(ga, gb)
    )


def expert_gemm_rows(rng, rows):
    for (E, C, D, F) in [(4, 256, 512, 1024), (8, 128, 256, 768)]:
        xe = jnp.asarray(rng.standard_normal((E, C, D)), jnp.bfloat16) * 0.3
        wg = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
        wu = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
        wd = jnp.asarray(rng.standard_normal((E, F, D)), jnp.bfloat16) * 0.05
        args = (xe, wg, wu, wd)
        ref = jax.jit(expert_gemm_ref)
        us_fwd = timed(ref, *args) * 1e6
        ref_loss = jax.jit(lambda *a: jnp.sum(jnp.square(expert_gemm_ref(*a))))
        us_bwd = timed(jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2, 3))), *args) * 1e6
        err = float(jnp.max(jnp.abs(
            expert_gemm(*args).astype(jnp.float32) - ref(*args).astype(jnp.float32)
        )))
        saved = 2 * E * C * F * 2 * 2  # gate+up bf16, write+read, bytes
        rows.append({
            "name": f"expert_gemm E{E} C{C} D{D} F{F}",
            "us_fwd_xla_ref": round(us_fwd, 1),
            "us_fwdbwd_xla_ref": round(us_bwd, 1),
            "kernel_max_err": round(err, 5),
            "gemm_rows": E * C,
            "activation_bytes": E * C * (D + F + D) * 2,
            "derived": f"fused epilogue saves {saved/1e6:.1f}MB HBM traffic/layer",
        })


def grouped_gemm_rows(rng, rows):
    """Fwd+bwd on the sorted dropless layout at the llama3-e8t2 routing
    shape, with the recompute residual accounting."""
    E, k, T, D, F = 8, 2, 1024, 256, 512
    N = T * k
    bc = 128
    wg = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
    wu = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
    wd = jnp.asarray(rng.standard_normal((E, F, D)), jnp.bfloat16) * 0.05
    gs = jnp.full((E,), N // E, jnp.int32)  # balanced routing
    xs = jnp.asarray(rng.standard_normal((N, D)), jnp.bfloat16) * 0.3
    args = (xs, wg, wu, wd)

    xla_loss = jax.jit(lambda *a: jnp.sum(jnp.square(grouped_gemm_xla(*a, gs))))
    us_fwd = timed(jax.jit(grouped_gemm_xla), *args, gs) * 1e6
    us_bwd = timed(jax.jit(jax.grad(xla_loss, argnums=(0, 1, 2, 3))), *args) * 1e6

    # gradient parity kernel vs XLA (N is already bc-aligned and balanced)
    k_loss = lambda *a: jnp.sum(jnp.square(grouped_gemm(*a, gs, row_block=bc)))
    grad_err = _grad_err(k_loss, lambda *a: xla_loss(*a), args)

    # recompute accounting: measured VJP residuals vs what autodiff keeps
    res = grouped_gemm_residuals(xs, wg, wu, wd, gs, blocks=(bc, 512, 512))
    residual_bytes = sum(int(np.prod(r.shape)) * r.dtype.itemsize for r in res)
    res_shapes = [tuple(r.shape) for r in res]
    assert (N, F) not in res_shapes, (
        f"recompute regression: (N, F) intermediate saved as residual: {res_shapes}"
    )
    xla_saved = 3 * N * F * 2  # gate, up, h in bf16 kept by plain autodiff
    rows.append({
        "name": f"grouped_gemm_bwd e8t2 N{N} D{D} F{F} bc{bc}",
        "us_fwd_xla_ref": round(us_fwd, 1),
        "us_fwdbwd_xla_ref": round(us_bwd, 1),
        "kernel_max_err": round(grad_err, 5),
        "gemm_rows": N,
        "activation_bytes": residual_bytes,
        "derived": (
            f"recompute saves {xla_saved/1e6:.1f}MB residuals/layer "
            f"(O(N*F) -> O(N*D): {residual_bytes/1e6:.1f}MB saved inputs)"
        ),
    })


def dispatcher_comparison(rng, rows):
    """Dropless expert-FFN cost, padded (E, C=T, D) layout vs. the sorted
    dispatcher's flat (T*k, D) layout, at the llama3-e8t2 routing shape
    (E=8, top_k=2; D/F reduced so the XLA baseline runs on CPU)."""
    E, k, T, D, F = 8, 2, 1024, 256, 512
    C = T  # padded dropless worst case: one expert could take every token
    wg = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
    wu = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
    wd = jnp.asarray(rng.standard_normal((E, F, D)), jnp.bfloat16) * 0.05

    xe = jnp.asarray(rng.standard_normal((E, C, D)), jnp.bfloat16) * 0.3
    us_pad = timed(jax.jit(expert_gemm_ref), xe, wg, wu, wd) * 1e6

    # balanced routing, as the load-balance loss drives it
    gs = jnp.full((E,), T * k // E, jnp.int32)
    xs = jnp.asarray(rng.standard_normal((T * k, D)), jnp.bfloat16) * 0.3
    us_sort = timed(jax.jit(grouped_gemm_xla), xs, wg, wu, wd, gs) * 1e6

    act_bytes = lambda rows_: rows_ * (D + F + D) * 2  # x in, h, y out (bf16)
    rows.append({
        "name": f"dispatch e8t2 padded-dropless E{E} C{C} D{D} F{F}",
        "us_fwd_xla_ref": round(us_pad, 1),
        "kernel_max_err": 0.0,
        "gemm_rows": E * C,
        "activation_bytes": act_bytes(E * C),
        "derived": f"{E*C} gemm rows, {act_bytes(E*C)/1e6:.1f}MB activations",
    })
    rows.append({
        "name": f"dispatch e8t2 sorted-dropless N{T*k} D{D} F{F}",
        "us_fwd_xla_ref": round(us_sort, 1),
        "kernel_max_err": 0.0,
        "gemm_rows": T * k,
        "activation_bytes": act_bytes(T * k),
        "derived": (
            f"{T*k} gemm rows, {act_bytes(T*k)/1e6:.1f}MB activations "
            f"({E*C/(T*k):.0f}x fewer rows than padded)"
        ),
    })


def quant_rows(rng, rows):
    """bf16 vs int8 streamed-operand bytes on BOTH fused-dequant paths.

    ``bytes_per_row`` counts the stationary operand each kernel streams
    from HBM per compute row — expert weights + per-channel scales per
    grouped-GEMM row, referenced KV pages + per-token scale sidecar per
    decode query. That is the term int8 shrinks (the activation traffic is
    identical across each pair, so including it would only dilute the
    ratio the quantization actually changes). Asserted here: >= 1.8x
    reduction on both paths — the bandwidth claim behind the quant flags."""
    from repro.core.quant import quantize_kv, quantize_weight
    from repro.kernels.ops import (
        grouped_gemm_q8,
        paged_attention,
        paged_attention_q8,
    )
    from repro.kernels.ref import (
        grouped_gemm_q8_ref,
        paged_attention_q8_ref,
        paged_attention_ref,
    )

    # -- grouped GEMM: int8 weights, bf16 activations -------------------------
    E, k, T, D, F = 8, 2, 1024, 256, 512
    N, bc = T * k, 128
    wg = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
    wu = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
    wd = jnp.asarray(rng.standard_normal((E, F, D)), jnp.bfloat16) * 0.05
    gs = jnp.full((E,), N // E, jnp.int32)
    xs = jnp.asarray(rng.standard_normal((N, D)), jnp.bfloat16) * 0.3

    (qg, sg), (qu, su), (qd, sd) = map(quantize_weight, (wg, wu, wd))
    qargs = (xs, qg, qu, qd, sg, su, sd)
    us_bf16 = timed(jax.jit(grouped_gemm_xla), xs, wg, wu, wd, gs) * 1e6
    us_q8 = timed(jax.jit(grouped_gemm_q8_ref), *qargs, gs) * 1e6
    err_bf16 = float(jnp.max(jnp.abs(
        grouped_gemm(xs, wg, wu, wd, gs, row_block=bc).astype(jnp.float32)
        - grouped_gemm_xla(xs, wg, wu, wd, gs).astype(jnp.float32))))
    err_q8 = float(jnp.max(jnp.abs(
        grouped_gemm_q8(*qargs, gs, row_block=bc).astype(jnp.float32)
        - grouped_gemm_q8_ref(*qargs, gs).astype(jnp.float32))))
    quant_err = float(jnp.max(jnp.abs(
        grouped_gemm_q8_ref(*qargs, gs).astype(jnp.float32)
        - grouped_gemm_xla(xs, wg, wu, wd, gs).astype(jnp.float32))))
    bpr_bf16 = E * 3 * D * F * 2 / N
    bpr_q8 = E * (3 * D * F * 1 + (2 * F + D) * 2) / N  # int8 + bf16 scales
    gemm_ratio = bpr_bf16 / bpr_q8
    for tag, us, err, bpr, extra in (
        ("bf16", us_bf16, err_bf16, bpr_bf16, "weight traffic baseline"),
        ("int8", us_q8, err_q8, bpr_q8,
         f"{gemm_ratio:.2f}x fewer weight bytes/row; "
         f"quant err {quant_err:.3f} vs bf16"),
    ):
        rows.append({
            "name": f"grouped_gemm_{tag} e8t2 N{N} D{D} F{F}",
            "us_fwd_xla_ref": round(us, 1),
            "kernel_max_err": round(err, 5),
            "gemm_rows": N,
            "activation_bytes": N * (D + F + D) * 2,
            "bytes_per_row": round(bpr, 1),
            "derived": extra,
        })
    assert gemm_ratio >= 1.8, (
        f"int8 grouped-GEMM weight bytes/row only {gemm_ratio:.2f}x smaller "
        f"(need >= 1.8x)"
    )

    # -- paged attention: int8 KV pages + f32 scale sidecar -------------------
    P, ps, B, H, KV, d = 32, 8, 4, 8, 2, 64
    maxP = 6
    kp = jnp.asarray(rng.standard_normal((P, ps, KV, d)), jnp.bfloat16) * 0.3
    vp = jnp.asarray(rng.standard_normal((P, ps, KV, d)), jnp.bfloat16) * 0.3
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.bfloat16) * 0.3
    bt = jnp.asarray(
        rng.permutation(P)[: B * maxP].reshape(B, maxP), jnp.int32
    )
    sl = jnp.asarray(rng.integers(ps, maxP * ps, B), jnp.int32)
    kq, ks = quantize_kv(kp)
    vq, vs = quantize_kv(vp)

    us_pa_bf16 = timed(jax.jit(paged_attention_ref), q, kp, vp, bt, sl) * 1e6
    us_pa_q8 = timed(jax.jit(paged_attention_q8_ref),
                     q, kq, vq, ks, vs, bt, sl) * 1e6
    err_pa_bf16 = float(jnp.max(jnp.abs(
        paged_attention(q, kp, vp, bt, sl).astype(jnp.float32)
        - paged_attention_ref(q, kp, vp, bt, sl).astype(jnp.float32))))
    err_pa_q8 = float(jnp.max(jnp.abs(
        paged_attention_q8(q, kq, vq, ks, vs, bt, sl).astype(jnp.float32)
        - paged_attention_q8_ref(q, kq, vq, ks, vs, bt, sl).astype(jnp.float32))))
    pa_quant_err = float(jnp.max(jnp.abs(
        paged_attention_q8_ref(q, kq, vq, ks, vs, bt, sl).astype(jnp.float32)
        - paged_attention_ref(q, kp, vp, bt, sl).astype(jnp.float32))))
    # per decode query: k+v entries of every referenced page (token x head)
    pa_bpr_bf16 = maxP * ps * KV * 2 * (d * 2)
    pa_bpr_q8 = maxP * ps * KV * 2 * (d * 1 + 4)  # int8 + f32 scale
    pa_ratio = pa_bpr_bf16 / pa_bpr_q8
    for tag, us, err, bpr, extra in (
        ("bf16", us_pa_bf16, err_pa_bf16, pa_bpr_bf16, "KV traffic baseline"),
        ("int8", us_pa_q8, err_pa_q8, pa_bpr_q8,
         f"{pa_ratio:.2f}x fewer KV bytes/query; "
         f"quant err {pa_quant_err:.3f} vs bf16"),
    ):
        rows.append({
            "name": f"paged_attn_{tag} P{P} ps{ps} B{B} H{H} KV{KV} d{d}",
            "us_fwd_xla_ref": round(us, 1),
            "kernel_max_err": round(err, 5),
            "gemm_rows": B * H,
            "activation_bytes": B * H * d * 2,
            "bytes_per_row": round(bpr, 1),
            "derived": extra,
        })
    assert pa_ratio >= 1.8, (
        f"int8 KV bytes/query only {pa_ratio:.2f}x smaller (need >= 1.8x)"
    )


def _sorted_routing(rng, E, k, T, bc):
    """Sorted-dispatcher index vectors for random top-k routing (distinct
    experts per token), mirroring SortedDispatcher._indices at row_block=bc."""
    N = T * k
    idx = np.stack([rng.permutation(E)[:k] for _ in range(T)])
    flat_e = jnp.asarray(idx.reshape(N).astype(np.int32))
    gates = jnp.asarray(rng.uniform(0.2, 1.0, size=(N,)).astype(np.float32))
    order = jnp.argsort(flat_e, stable=True)
    token = (order // k).astype(jnp.int32)
    slot = (order % k).astype(jnp.int32)
    sorted_e = flat_e[order]
    gs = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    padded = ((gs + bc - 1) // bc) * bc
    starts_pad = jnp.cumsum(padded) - padded
    starts = jnp.cumsum(gs) - gs
    pos = jnp.arange(N, dtype=jnp.int32) - starts[sorted_e]
    dest = (starts_pad[sorted_e] + pos).astype(jnp.int32)
    return token, slot, dest, gates[order], gs


def fused_dispatch_section(rng):
    """Dispatch-in-kernel vs materializing dispatch.

    Parity is measured by running the fused Pallas kernel against the
    unfused composition (scatter -> grouped GEMM -> fp32 gather/combine) on
    a routed batch; the HBM dispatch-buffer accounting is analytic at the
    llama3-e8t2 nominal shape and counts only the buffers the fusion
    removes: the permuted (N_pad, D) input and the (N_pad, D) expert
    output, each written once and read once in bf16, vs the fused path's
    (k*T, D) bf16 slot partials, the pass that packs x's bf16 rows into
    32-bit words for the row DMAs (one read, one write of (T, D) bf16), and
    the int32/f32 scalar-prefetch vectors. Asserted: fused traffic strictly
    below unfused."""
    from repro.kernels.expert_gemm import _aligned_rows, _fused_unfused_ref
    from repro.kernels.ops import grouped_gemm_fused

    E, k, D, F, bc = 8, 2, 256, 512, 128
    T = 64  # parity shape kept small: interpret-mode grid is nt*nf*nd*bc
    token, slot, dest, gate_sorted, gs = _sorted_routing(rng, E, k, T, bc)
    wg = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
    wu = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
    wd = jnp.asarray(rng.standard_normal((E, F, D)), jnp.bfloat16) * 0.05
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.bfloat16) * 0.3
    y_fused = grouped_gemm_fused(
        x, wg, wu, wd, gs, token, dest, slot, gate_sorted, row_block=bc
    )
    y_ref = _fused_unfused_ref(
        x, wg, wu, wd, gs, token, dest, slot, gate_sorted, (bc, 512, 256), True
    )
    err = float(jnp.max(jnp.abs(
        y_fused.astype(jnp.float32) - y_ref.astype(jnp.float32)
    )))

    # traffic accounting at the nominal serving shape (balanced routing)
    Tn = 1024
    Nn = Tn * k
    Nn_pad = _aligned_rows(Nn, E, bc)
    # unfused: xs scatter-write + kernel read, ys kernel-write + gather read
    unfused_bytes = 2 * 2 * Nn_pad * D * 2
    # fused: slot partials (k*T, D) bf16 write + read, the x word-packing
    # pass, and the tok_pad/row_out (int32) and gate_pad (f32) vectors
    fused_bytes = (2 * k * Tn * D * 2 * 2 + 2 * Tn * D * 2
                   + 2 * 3 * Nn_pad * 4)
    assert fused_bytes < unfused_bytes, (
        f"fused dispatch traffic {fused_bytes} not below unfused "
        f"{unfused_bytes}"
    )
    section = {
        "name": f"fused_dispatch e8t2 N{Nn} D{D} bc{bc}",
        "parity_err": round(err, 5),
        "dispatch_bytes_unfused": unfused_bytes,
        "dispatch_bytes_fused": fused_bytes,
        "traffic_ratio": round(unfused_bytes / fused_bytes, 2),
        "fused_strictly_lower": fused_bytes < unfused_bytes,
    }
    print(f"# fused_dispatch: {section['traffic_ratio']:.2f}x less "
          f"dispatch-buffer HBM traffic, parity err {err:.5f}")
    return section


def autotune_section():
    """Autotuner evidence: tuned-vs-heuristic modeled kernel time on the
    grouped-GEMM traffic model, plus cache determinism (the second resolve
    must be a pure memo hit). Runs against a throwaway cache dir so the
    bench neither touches nor depends on the user's persisted winners."""
    import shutil
    import tempfile

    from repro.kernels import autotune as at
    from repro.kernels import expert_gemm as eg
    from repro.kernels.ops import _gg_cost, _tuned_ffn_blocks

    E, D, F, bc = 8, 256, 512, 128
    saved = {kk: os.environ.get(kk) for kk in
             ("REPRO_AUTOTUNE", "REPRO_AUTOTUNE_CACHE", "REPRO_HW_PROFILE")}
    tmpdir = tempfile.mkdtemp(prefix="repro_bench_tune_")
    os.environ["REPRO_AUTOTUNE"] = "1"
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(tmpdir, "cache.json")
    os.environ["REPRO_HW_PROFILE"] = "v5e"
    at.reset()
    try:
        fallback = tuple(
            eg._pick(b, d) for b, d in zip(eg.DEFAULT_BLOCKS[1:], (F, D))
        )
        cost = _gg_cost(E, D, F, bc, 2)
        # first resolve: served by the committed autotune_defaults.json (a
        # disk hit) or a fresh modeled search; second: pure memo hit
        _, bf, bd = _tuned_ffn_blocks("grouped_gemm", E, D, F, bc, 2)
        misses = at.stats()["misses"]
        _, bf2, bd2 = _tuned_ffn_blocks("grouped_gemm", E, D, F, bc, 2)
        hits = at.stats()["hits"]
        assert (bf2, bd2) == (bf, bd), "autotune cache not deterministic"
        c_fb, c_tu = cost(fallback), cost((bf, bd))
        us_fb = at.modeled_seconds(
            c_fb["flops"], c_fb["bytes"], c_fb["steps"]) * 1e6
        us_tu = at.modeled_seconds(
            c_tu["flops"], c_tu["bytes"], c_tu["steps"]) * 1e6
        assert us_tu <= us_fb + 1e-9, (
            f"tuned blocks modeled slower than heuristic: {us_tu} > {us_fb}"
        )
        section = {
            "name": f"autotune grouped_gemm e8 D{D} F{F} bc{bc}",
            "fallback_blocks": list(fallback),
            "tuned_blocks": [int(bf), int(bd)],
            "modeled_us_fallback": round(us_fb, 2),
            "modeled_us_tuned": round(us_tu, 2),
            "cache_misses": int(misses),
            "cache_hits": int(hits),
            "tuned_no_worse": bool(us_tu <= us_fb + 1e-9),
        }
        print(f"# autotune: {list(fallback)} -> {section['tuned_blocks']} "
              f"modeled {us_fb:.1f}us -> {us_tu:.1f}us, "
              f"{hits} cache hit(s) / {misses} miss(es) across resolves")
        return section
    finally:
        for kk, vv in saved.items():
            if vv is None:
                os.environ.pop(kk, None)
            else:
                os.environ[kk] = vv
        at.reset()
        shutil.rmtree(tmpdir, ignore_errors=True)


def flash_rows(rng, rows):
    for (B, S, H, KV, d) in [(2, 1024, 8, 2, 128), (1, 2048, 4, 4, 64)]:
        q = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.bfloat16) * 0.3
        k = jnp.asarray(rng.standard_normal((B, S, KV, d)), jnp.bfloat16) * 0.3
        v = jnp.asarray(rng.standard_normal((B, S, KV, d)), jnp.bfloat16) * 0.3
        kb, vb = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
        ref = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v, causal=True))
        ref_loss = jax.jit(
            lambda q, k, v: jnp.sum(jnp.square(flash_attention_ref(q, k, v, causal=True)))
        )
        us_fwd = timed(ref, q, kb, vb) * 1e6
        us_bwd = timed(jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2))), q, kb, vb) * 1e6
        y = flash_attention(q, k, v, causal=True)
        err = float(jnp.max(jnp.abs(
            y.astype(jnp.float32) - ref(q, kb, vb).astype(jnp.float32)
        )))
        hbm_scores = B * H * S * S * 4 / 1e6
        # bwd residuals: q,k,v,out (bf16) + lse (f32); autodiff of the dense
        # ref would also keep the (B,H,S,S) probability matrix
        lse_bytes = B * H * S * 4
        rows.append({
            "name": f"flash_attn B{B} S{S} H{H} KV{KV} d{d}",
            "us_fwd_xla_ref": round(us_fwd, 1),
            "us_fwdbwd_xla_ref": round(us_bwd, 1),
            "kernel_max_err": round(err, 5),
            "gemm_rows": B * H * S,
            "activation_bytes": lse_bytes,
            "derived": (
                f"avoids {hbm_scores:.0f}MB fp32 score materialization "
                f"fwd+bwd; lse residual {lse_bytes/1e3:.0f}KB"
            ),
        })


def main():
    rng = np.random.default_rng(0)
    rows = []
    expert_gemm_rows(rng, rows)
    grouped_gemm_rows(rng, rows)
    dispatcher_comparison(rng, rows)
    quant_rows(rng, rows)
    flash_rows(rng, rows)
    fused = fused_dispatch_section(rng)
    tune = autotune_section()
    keys = ["name", "us_fwd_xla_ref", "us_fwdbwd_xla_ref", "kernel_max_err",
            "gemm_rows", "activation_bytes", "bytes_per_row", "derived"]
    emit("kernel_bench", rows, keys)
    with open(ROOT_JSON, "w") as f:
        json.dump({"schema": keys, "rows": rows,
                   "fused_dispatch": fused, "autotune": tune}, f, indent=1)
    print(f"# wrote {ROOT_JSON}")


if __name__ == "__main__":
    main()
