"""Pallas TPU paged-attention decode kernel.

Single-token decode against a block-table KV cache: the KV pool lives in HBM
as ``(num_pages, KV, page_size, d)`` shared by every sequence, and each
sequence owns an ordered page list ``block_table[b]`` (logical slot ``j`` of
KV head ``h`` maps to ``pool[block_table[b, j // page_size], h, j %
page_size]`` — identity position mapping, pages never wrap). A KV head's
``(page_size, d)`` tile is the minor pair of the pool, so one grid step's
block is a whole TPU tile run rather than a strided row slice.

The gather happens *inside* the kernel: ``block_table`` and ``seq_lens`` are
scalar-prefetched (``PrefetchScalarGridSpec``) so the BlockSpec index map
resolves the physical page for grid step ``(b, kv, j)`` before the body
runs, and the pipeline DMAs exactly one ``(page_size, d)`` KV tile per step
— no ``(B, max_pages * page_size, KV, d)`` gathered copy is ever
materialized in HBM (the XLA reference path in ``kernels/ref.py`` does
materialize it; that is the memory trade this kernel exists to avoid).

GQA: the grid iterates KV heads and each step computes all ``G = H // KV``
query heads that share the KV head, so the pool is read once per KV head.
Softmax is the standard logsumexp-stable online update with fp32 ``m/l/acc``
carried in VMEM scratch across the page dimension (innermost grid axis).

Dead pages are skipped: ``block_table`` entries of -1 (unallocated, or
released because a sliding window moved past them) and pages at or past
``seq_lens[b]`` cost no compute or DMA-decode bandwidth beyond the (tiny)
scalar test.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(
    bt_ref, len_ref,  # scalar-prefetched: (B, maxP) page ids, (B,) lengths
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, ps: int, maxP: int, bps: int, nsub: int,
    window: Optional[int], scale: float,
):
    # innermost grid axis walks sub-page tiles: step j covers rows
    # [js*bps, (js+1)*bps) of page jp (bps == ps -> one step per page)
    b, j = pl.program_id(0), pl.program_id(2)
    jp, js = j // nsub, j % nsub
    start = jp * ps + js * bps  # logical position of the tile's first row

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page = bt_ref[b, jp]
    n = len_ref[b]  # valid tokens incl. the current one; query pos = n - 1
    live = jnp.logical_and(page >= 0, start < n)
    if window is not None:
        # whole tile below the window start contributes nothing
        live = jnp.logical_and(live, start + bps - 1 > n - 1 - window)

    def _compute():
        q = q_ref[0, 0]  # (G, d)
        k = k_ref[0, 0]  # (bps, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G, bps)
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, bps), 1)
        mask = kpos < n
        if window is not None:
            mask = jnp.logical_and(mask, kpos > n - 1 - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]  # (G, 1) columns
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0, 0], preferred_element_type=jnp.float32
        )

    pl.when(live)(_compute)

    @pl.when(j == maxP * nsub - 1)
    def _write():
        # fully-masked sequences (l == 0) emit zeros, matching the oracle
        l = l_scr[...]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = jnp.where(l > 0, acc_scr[...] / safe, 0.0).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret", "page_block")
)
def paged_attention(
    q: jax.Array,  # (B, H, d) one query token per sequence
    k_pool: jax.Array,  # (num_pages, KV, page_size, d)
    v_pool: jax.Array,  # (num_pages, KV, page_size, d)
    block_table: jax.Array,  # (B, max_pages) int32, -1 = unassigned
    seq_lens: jax.Array,  # (B,) int32 valid tokens (incl. current)
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
    page_block: Optional[int] = None,
) -> jax.Array:
    B, H, d = q.shape
    num_pages, KV, ps, _ = k_pool.shape
    maxP = block_table.shape[1]
    G = H // KV
    assert H % KV == 0, (H, KV)
    scale = float(scale) if scale is not None else d**-0.5
    # sub-page KV tile (autotunable): bps rows DMA'd per grid step. The
    # default — one whole page per step — preserves the original schedule.
    bps = int(page_block) if page_block else ps
    assert ps % bps == 0, (ps, bps)
    nsub = ps // bps

    qg = q.reshape(B, KV, G, d)
    bt = block_table.astype(jnp.int32)
    sl = seq_lens.astype(jnp.int32)
    _page = lambda b, kv, j, bt, sl: (
        jnp.maximum(bt[b, j // nsub], 0), kv, j % nsub, 0
    )

    out = pl.pallas_call(
        functools.partial(
            _pa_kernel, ps=ps, maxP=maxP, bps=bps, nsub=nsub,
            window=window, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, maxP * nsub),
            in_specs=[
                pl.BlockSpec((1, 1, G, d), lambda b, kv, j, bt, sl: (b, kv, 0, 0)),
                pl.BlockSpec((1, 1, bps, d), _page),
                pl.BlockSpec((1, 1, bps, d), _page),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, G, d), lambda b, kv, j, bt, sl: (b, kv, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, d), q.dtype),
        interpret=interpret,
    )(bt, sl, qg, k_pool, v_pool)
    return out.reshape(B, H, d)


# ---------------------------------------------------------------------------
# int8 KV pages: dequant fused into the per-page tile
#
# Pages hold int8 KV with a per-token, per-kv-head f32 scale sidecar shaped
# like the page payload with the head dim collapsed to 1 (serving/kv_cache
# keeps the sidecar leaves in the same pool tree so COW/defrag/DP-sharding
# move scales with their pages). The kernel resolves pages through the same
# scalar-prefetched block tables and dequantizes each (page_size, d) tile in
# VMEM right after the DMA: k/v int8 loads halve the HBM stream, scores and
# the weighted-value accumulation run in f32 (int8 values are exact in f32,
# so parity vs the dequantize-then-attend oracle is accumulation-order only).
# ---------------------------------------------------------------------------


def _pa_kernel_q8(
    bt_ref, len_ref,
    q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr,
    *, ps: int, maxP: int, bps: int, nsub: int,
    window: Optional[int], scale: float,
):
    b, j = pl.program_id(0), pl.program_id(2)
    jp, js = j // nsub, j % nsub
    start = jp * ps + js * bps

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page = bt_ref[b, jp]
    n = len_ref[b]
    live = jnp.logical_and(page >= 0, start < n)
    if window is not None:
        live = jnp.logical_and(live, start + bps - 1 > n - 1 - window)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, d)
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]  # (bps, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, bps), 1)
        mask = kpos < n
        if window is not None:
            mask = jnp.logical_and(mask, kpos > n - 1 - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]  # (G, 1) columns
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]  # (bps, d)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )

    pl.when(live)(_compute)

    @pl.when(j == maxP * nsub - 1)
    def _write():
        l = l_scr[...]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = jnp.where(l > 0, acc_scr[...] / safe, 0.0).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret", "page_block")
)
def paged_attention_q8(
    q: jax.Array,  # (B, H, d) one query token per sequence
    k_pool: jax.Array,  # (num_pages, KV, page_size, d) int8
    v_pool: jax.Array,  # (num_pages, KV, page_size, d) int8
    k_scale: jax.Array,  # (num_pages, KV, page_size, 1) f32 sidecar
    v_scale: jax.Array,  # (num_pages, KV, page_size, 1)
    block_table: jax.Array,  # (B, max_pages) int32, -1 = unassigned
    seq_lens: jax.Array,  # (B,) int32 valid tokens (incl. current)
    window: Optional[int] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
    page_block: Optional[int] = None,
) -> jax.Array:
    B, H, d = q.shape
    num_pages, KV, ps, _ = k_pool.shape
    maxP = block_table.shape[1]
    G = H // KV
    assert H % KV == 0, (H, KV)
    assert k_scale.shape == (num_pages, KV, ps, 1), k_scale.shape
    scale = float(scale) if scale is not None else d**-0.5
    bps = int(page_block) if page_block else ps
    assert ps % bps == 0, (ps, bps)
    nsub = ps // bps

    qg = q.reshape(B, KV, G, d)
    bt = block_table.astype(jnp.int32)
    sl = seq_lens.astype(jnp.int32)
    _page = lambda b, kv, j, bt, sl: (
        jnp.maximum(bt[b, j // nsub], 0), kv, j % nsub, 0
    )

    out = pl.pallas_call(
        functools.partial(
            _pa_kernel_q8, ps=ps, maxP=maxP, bps=bps, nsub=nsub,
            window=window, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, maxP * nsub),
            in_specs=[
                pl.BlockSpec((1, 1, G, d), lambda b, kv, j, bt, sl: (b, kv, 0, 0)),
                pl.BlockSpec((1, 1, bps, d), _page),
                pl.BlockSpec((1, 1, bps, d), _page),
                pl.BlockSpec((1, 1, bps, 1), _page),
                pl.BlockSpec((1, 1, bps, 1), _page),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, G, d), lambda b, kv, j, bt, sl: (b, kv, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, d), q.dtype),
        interpret=interpret,
    )(bt, sl, qg, k_pool, v_pool, k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
    return out.reshape(B, H, d)
