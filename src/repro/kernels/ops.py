"""jit'd public wrappers for the Pallas kernels.

On a TPU the kernels compile to Mosaic. On the CPU backend they execute in
interpret mode — the kernel body runs in Python for correctness validation
(the test suite); any other backend is an error, so a kernel path can
never silently degrade to the interpreter on an accelerator. The dispatch
subsystem (``core/dispatch``) calls
``expert_gemm`` (padded layout) or ``grouped_gemm`` (sorted layout) when
``use_kernel=True``; ``models/attention.py`` calls ``flash_attention`` in
place of the blockwise XLA path. All three are differentiable
(``jax.custom_vjp`` with hand-written backward Pallas kernels and
activation recompute — see kernels/expert_gemm.py, kernels/
flash_attention.py), so ``Trainer(use_kernel=True)`` runs forward AND
backward on the kernel path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _at
from repro.kernels import expert_gemm as _eg
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels target the TPU (interpret mode on the CPU for "
        f"tests); backend {backend!r} has neither"
    )


# ---------------------------------------------------------------------------
# Autotune hooks: analytic per-candidate traffic models handed to
# kernels/autotune.get_blocks. Everything here is shapes-only (static under
# jit tracing); with REPRO_AUTOTUNE off, get_blocks returns the static
# heuristic fallback untouched.
# ---------------------------------------------------------------------------

_GG_NOMINAL_ROWS = 4096  # nominal sorted-buffer rows for the traffic model


def _gg_cost(E: int, D: int, F: int, bc: int, w_it: int):
    """Traffic model of the two grouped-GEMM Pallas kernels at tiling
    (bc, bf, bd): expert weights are re-read once per row tile, x once per
    F tile, h written once and re-read once per D tile."""
    N = _GG_NOMINAL_ROWS

    def cost(blocks):
        bf, bd = blocks
        nf, nd, nt = F // bf, D // bd, max(N // bc, 1)
        gate_up_vmem = (
            bc * bd * 2.0 + 2.0 * bd * bf * w_it + 2.0 * bc * bf * 4.0
            + bc * bf * 2.0
        )
        down_vmem = bc * bf * 2.0 + bf * bd * w_it + bc * bd * 4.0 + bc * bd * 2.0
        return {
            "flops": 6.0 * N * D * F,
            "bytes": (
                nt * 3.0 * D * F * w_it
                + nf * N * D * 2.0
                + (1.0 + nd) * N * F * 2.0
                + N * D * 2.0
            ),
            "steps": 2.0 * nt * nf * nd,
            "vmem_bytes": max(gate_up_vmem, down_vmem),
        }

    return cost


def _tuned_ffn_blocks(kernel: str, E: int, D: int, F: int, row_block: int,
                      itemsize: int):
    """Resolve (row_block, bf, bd) for the grouped/fused expert kernels:
    row_block is structural (it is the sorted buffer's alignment, not a
    free tile), so only the lane tiles (bf, bd) are tuned."""
    fallback = tuple(
        _eg._pick(b, d) for b, d in zip(_eg.DEFAULT_BLOCKS[1:], (F, D))
    )
    bf, bd = _at.get_blocks(
        kernel,
        _at.make_key(kernel, E=E, D=D, F=F, itemsize=itemsize,
                     extra=f"bc{row_block}"),
        fallback,
        dims=(F, D),
        aligns=(128, 128),
        cost=_gg_cost(E, D, F, row_block, itemsize),
    )
    return (row_block, bf, bd)


def expert_gemm(xe, w_gate, w_up, w_down, blocks=_eg.DEFAULT_BLOCKS):
    """(..., E, C, D) x (E,D,F)x2 x (E,F,D) -> (..., E, C, D)."""
    lead = xe.shape[:-3]
    E, C, D = xe.shape[-3:]
    F = w_gate.shape[-1]
    blocks = (blocks[0],) + _tuned_ffn_blocks(
        "expert_gemm", E, D, F, blocks[0], itemsize=2
    )[1:]
    x3 = xe.reshape((-1, C, D)) if lead else xe
    if lead:
        G = x3.shape[0] // E if E else 1
        # fold leading group dims into the token dim per expert
        x3 = xe.reshape((-1, E, C, D)).transpose(1, 0, 2, 3).reshape(E, -1, D)
        y = _eg.expert_gemm(x3, w_gate, w_up, w_down, blocks=blocks, interpret=_interpret())
        y = y.reshape(E, -1, C, D).transpose(1, 0, 2, 3).reshape(lead + (E, C, D))
        return y
    return _eg.expert_gemm(xe, w_gate, w_up, w_down, blocks=blocks, interpret=_interpret())


def grouped_gemm(xs, w_gate, w_up, w_down, group_sizes, row_block=_eg.DEFAULT_BLOCKS[0]):
    """Group-size-aware grouped GEMM over the flat expert-sorted layout the
    sorted dispatcher produces: (N_pad, D) rows, each expert's region
    row_block-aligned, group_sizes (E,) valid rows per expert."""
    E, D = w_gate.shape[0], w_gate.shape[1]
    blocks = _tuned_ffn_blocks(
        "grouped_gemm", E, D, w_gate.shape[2], row_block, itemsize=2
    )
    return _eg.grouped_gemm(
        xs, w_gate, w_up, w_down, group_sizes, blocks=blocks, interpret=_interpret()
    )


def grouped_gemm_fused(x, w_gate, w_up, w_down, group_sizes, token, dest,
                       slot, gate_sorted, row_block=_eg.DEFAULT_BLOCKS[0]):
    """Dispatch-in-kernel sorted MoE FFN (token-major (T, D) in and out):
    the scalar-prefetched ``token``/``dest`` row indices resolve the gather
    in the gate/up prologue and the gate-weighted combine in the down
    epilogue — see kernels/expert_gemm.grouped_gemm_fused."""
    E, D = w_gate.shape[0], w_gate.shape[1]
    blocks = _tuned_ffn_blocks(
        "grouped_gemm_fused", E, D, w_gate.shape[2], row_block, itemsize=2
    )
    return _eg.grouped_gemm_fused(
        x, w_gate, w_up, w_down, group_sizes, token, dest, slot, gate_sorted,
        blocks=blocks, interpret=_interpret(),
    )


def grouped_gemm_fused_q8(x, w_gate, w_up, w_down, s_gate, s_up, s_down,
                          group_sizes, token, dest, slot, gate_sorted,
                          row_block=_eg.DEFAULT_BLOCKS[0]):
    """int8-weight fused-dispatch sorted MoE FFN (fused dequant; serving,
    forward-only)."""
    E, D = w_gate.shape[0], w_gate.shape[1]
    blocks = _tuned_ffn_blocks(
        "grouped_gemm_fused_q8", E, D, w_gate.shape[2], row_block, itemsize=1
    )
    return _eg.grouped_gemm_fused_q8(
        x, w_gate, w_up, w_down, s_gate, s_up, s_down, group_sizes,
        token, dest, slot, gate_sorted, blocks=blocks, interpret=_interpret(),
    )


def expert_gemm_q8(xe, w_gate, w_up, w_down, s_gate, s_up, s_down,
                   blocks=_eg.DEFAULT_BLOCKS):
    """int8-weight padded expert FFN with dequant fused into the tile:
    weights int8 (core/quant.py layout), per-expert per-output-channel
    scales applied to the fp32 accumulator in the epilogue. Forward-only
    (serving); same leading-dim folding as :func:`expert_gemm`."""
    lead = xe.shape[:-3]
    E, C, D = xe.shape[-3:]
    blocks = (blocks[0],) + _tuned_ffn_blocks(
        "expert_gemm_q8", E, D, w_gate.shape[-1], blocks[0], itemsize=1
    )[1:]
    if lead:
        x3 = xe.reshape((-1, E, C, D)).transpose(1, 0, 2, 3).reshape(E, -1, D)
        y = _eg.expert_gemm_q8(
            x3, w_gate, w_up, w_down, s_gate, s_up, s_down,
            blocks=blocks, interpret=_interpret(),
        )
        return y.reshape(E, -1, C, D).transpose(1, 0, 2, 3).reshape(lead + (E, C, D))
    return _eg.expert_gemm_q8(
        xe, w_gate, w_up, w_down, s_gate, s_up, s_down,
        blocks=blocks, interpret=_interpret(),
    )


def grouped_gemm_q8(xs, w_gate, w_up, w_down, s_gate, s_up, s_down,
                    group_sizes, row_block=_eg.DEFAULT_BLOCKS[0]):
    """int8-weight grouped GEMM over the sorted layout (fused dequant,
    fp32 accumulate, SwiGLU epilogue unchanged). Forward-only."""
    E, D = w_gate.shape[0], w_gate.shape[1]
    blocks = _tuned_ffn_blocks(
        "grouped_gemm_q8", E, D, w_gate.shape[2], row_block, itemsize=1
    )
    return _eg.grouped_gemm_q8(
        xs, w_gate, w_up, w_down, s_gate, s_up, s_down, group_sizes,
        blocks=blocks, interpret=_interpret(),
    )


def grouped_gemm_xla(xs, w_gate, w_up, w_down, group_sizes):
    """XLA path for the sorted layout (compact buffer, row_block=1):
    ``lax.ragged_dot`` is the native grouped GEMM."""
    g = jax.lax.ragged_dot(xs, w_gate, group_sizes)
    u = jax.lax.ragged_dot(xs, w_up, group_sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
    return jax.lax.ragged_dot(h, w_down, group_sizes)


def _fa_cost(B: int, H: int, Sq: int, Sk: int, d: int, causal: bool,
             window: Optional[int], it: int):
    """Flash-attention forward model at (bq, bk), over the live-tile
    schedule that runs (``flash_schedule``): each grid step of a query head
    multiplies one (bq, bk) tile and fetches one K and one V block; q is
    read and out written once."""

    def cost(blocks):
        bq, bk = blocks
        sched = _fa.flash_schedule(Sq, Sk, bq, bk, causal, window)
        steps = B * H * sched.steps["fwd"]
        return {
            "flops": 4.0 * B * H * sched.live * bq * bk * d,
            "bytes": B * H * Sq * d * 2.0 * it + steps * 2.0 * bk * d * it,
            "steps": float(steps),
            "vmem_bytes": _fa.vmem_bytes(bq, bk, d, it),
        }

    return cost


def flash_attention(
    q, k, v, causal: bool = True, window: Optional[int] = None,
    scale: Optional[float] = None, blocks=None,
):
    """``blocks`` bounds the tiles; None takes ``flash_tiles`` of the
    shape. With tuning on, the tuner scores candidates by ``_fa_cost``."""
    B, Sq, H, d = q.shape
    Sk = k.shape[1]
    it = q.dtype.itemsize
    blocks = _at.get_blocks(
        "flash_attention",
        _at.make_key("flash_attention", D=d, itemsize=it,
                     extra=f"Sq{Sq}xSk{Sk}c{int(causal)}w{window}"),
        _fa.flash_tiles(Sq, Sk, d, it) if blocks is None
        else _fa._tiling(Sq, Sk, blocks),
        dims=(Sq, Sk),
        aligns=(8, 8),
        cost=_fa_cost(B, H, Sq, Sk, d, causal, window, it),
    )
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, scale=scale, blocks=blocks,
        interpret=_interpret(),
    )


def _pa_cost(B: int, KV: int, G: int, maxP: int, ps: int, d: int, it: int):
    """Paged-decode traffic model at sub-page tile (bps,): total KV bytes
    are tiling-invariant (every live row is read once); the tile size
    trades grid-step overhead against VMEM footprint."""

    def cost(blocks):
        (bps,) = blocks
        nsub = ps // bps
        kv_bytes = B * KV * maxP * ps * d * float(it) * 2.0
        scale_bytes = (B * KV * maxP * ps * 4.0 * 2.0) if it == 1 else 0.0
        return {
            "flops": 4.0 * B * KV * G * maxP * ps * d,
            "bytes": kv_bytes + scale_bytes + B * KV * G * d * 2.0 * 2.0,
            "steps": float(B * KV * maxP * nsub),
            "vmem_bytes": (
                2.0 * bps * d * float(it) + G * d * 2.0 + G * d * 4.0
                + 2.0 * G * 4.0 + (2.0 * bps * 4.0 if it == 1 else 0.0)
            ),
        }

    return cost


def _pa_page_block(kernel: str, q, k_pool, block_table, itemsize: int):
    B, H, d = q.shape
    _, KV, ps, _ = k_pool.shape
    maxP = block_table.shape[1]
    (bps,) = _at.get_blocks(
        kernel,
        _at.make_key(kernel, k=KV, D=d, page_size=ps, itemsize=itemsize,
                     extra=f"G{H // KV}"),
        (ps,),
        dims=(ps,),
        aligns=(8,),
        cost=_pa_cost(B, KV, H // KV, maxP, ps, d, itemsize),
    )
    return bps


def paged_attention(
    q, k_pool, v_pool, block_table, seq_lens,
    window: Optional[int] = None, scale: Optional[float] = None,
):
    """Single-token decode against the block-table KV pool: q (B,H,d),
    pools (num_pages, KV, page_size, d), block_table (B, max_pages) int32
    (-1 = unassigned), seq_lens (B,). The page gather happens inside the
    kernel via scalar-prefetched block tables."""
    bps = _pa_page_block("paged_attention", q, k_pool, block_table,
                         k_pool.dtype.itemsize)
    return _pa.paged_attention(
        q, k_pool, v_pool, block_table, seq_lens, window=window, scale=scale,
        interpret=_interpret(), page_block=bps,
    )


def paged_attention_q8(
    q, k_pool, v_pool, k_scale, v_scale, block_table, seq_lens,
    window: Optional[int] = None, scale: Optional[float] = None,
):
    """int8-KV decode: pools are int8 with per-token/kv-head f32 scale
    sidecars shaped (num_pages, KV, page_size, 1); the kernel dequantizes
    each page tile in VMEM after the scalar-prefetched block-table DMA."""
    bps = _pa_page_block("paged_attention_q8", q, k_pool, block_table, 1)
    return _pa.paged_attention_q8(
        q, k_pool, v_pool, k_scale, v_scale, block_table, seq_lens,
        window=window, scale=scale, interpret=_interpret(), page_block=bps,
    )
