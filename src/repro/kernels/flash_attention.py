"""Pallas TPU flash attention (causal / sliding-window), GQA-aware,
differentiable.

Forward: online-softmax over KV blocks with fp32 m/l/acc carried in VMEM
scratch — the TPU-tiled version of the blockwise XLA path in
models/attention.py. GQA reads the shared KV head via the BlockSpec index
map (kv = h // group) instead of materializing a broadcast copy in HBM. The
forward also emits the logsumexp (B*H, Sq, 1) — the only extra residual
the backward needs. Per-row softmax statistics (m, l, lse, delta) are
``(rows, 1)`` columns throughout: the TPU tiles the last two dims of every
block, so a full trailing dim of 1 is legal where a ``(1, bq)`` row slice of
a 2-D array is not.

Backward: the standard two-pass flash schedule behind ``jax.custom_vjp``.
Residuals are (q, k, v, out, lse); the (Sq, Sk) probability blocks are
RECOMPUTED per tile from ``lse``, never stored:

* dq kernel — grid (B*H, nq, nk): p = exp(s - lse), dp = do @ v^T,
  ds = p * (dp - delta) * scale, dq += ds @ k, accumulated over KV blocks
  in fp32 scratch.
* dk/dv kernel — grid (B*KV, nk, G, nq): same recompute per (q-block,
  group-head) pair; dk/dv accumulate over the G query heads sharing the KV
  head and over q blocks in fp32 scratch (inner grid dims), so GQA needs no
  (B*H, Sk, d) staging buffer.

``delta = rowsum(do * out)`` (the softmax Jacobian diagonal) is computed
outside the kernels — it is O(N*d) elementwise.

Block sizes (bq, bk) default to (128, 512): q tile (128 x d) and kv tiles
(512 x d) sit in VMEM alongside the fp32 acc (128 x d) — ~1.2 MB at
d=128, far under the ~16 MB VMEM budget, leaving room for double-buffered
pipelining of the kv stream from HBM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCKS = (128, 512)
NEG_INF = -1e30


def _positions(qi, ki, bq: int, bk: int, q_offset: int):
    """(bq, bk) query/key position grids for the (qi, ki) tile."""
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return qpos, kpos


def _tile_mask(qpos, kpos, causal: bool, window: Optional[int]):
    mask = jnp.ones(qpos.shape, jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def _tile_relevant(qi, ki, bq: int, bk: int, q_offset: int,
                   causal: bool, window: Optional[int]):
    """Traced predicate: does the (qi, ki) tile contain ANY unmasked entry?
    Fully-masked tiles contribute nothing (p == 0 everywhere) and are
    skipped — under causal masking that halves fwd/bwd attention FLOPs.
    Returns None when every tile is live (no mask)."""
    rel = None
    if causal:  # some kpos <= qpos: min kpos vs max qpos
        rel = qi * bq + bq - 1 + q_offset >= ki * bk
    if window is not None:  # some kpos > qpos - window: max kpos vs min qpos
        w = ki * bk + bk - 1 > qi * bq + q_offset - window
        rel = w if rel is None else jnp.logical_and(rel, w)
    return rel


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int],
    bq: int, bk: int, nk: int, q_offset: int,
):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qi = pl.program_id(1)

    def _compute():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)

        qpos, kpos = _positions(qi, kb, bq, bk, q_offset)
        s = jnp.where(_tile_mask(qpos, kpos, causal, window), s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]  # (bq, 1) columns
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
        )

    rel = _tile_relevant(qi, kb, bq, bk, q_offset, causal, window)
    if rel is None:
        _compute()
    else:
        pl.when(rel)(_compute)

    @pl.when(kb == nk - 1)
    def _write():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _fa_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale: float, causal: bool, window: Optional[int],
    bq: int, bk: int, nk: int, q_offset: int,
):
    kb, qi = pl.program_id(2), pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos, kpos = _positions(qi, kb, bq, bk, q_offset)
        s = jnp.where(_tile_mask(qpos, kpos, causal, window), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])  # (bq, bk), masked entries -> 0
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_acc[...] += jnp.dot(
            ds, k.astype(jnp.float32), preferred_element_type=jnp.float32
        )

    rel = _tile_relevant(qi, kb, bq, bk, q_offset, causal, window)
    if rel is None:
        _compute()
    else:
        pl.when(rel)(_compute)

    @pl.when(kb == nk - 1)
    def _write():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, causal: bool, window: Optional[int],
    bq: int, bk: int, nq: int, G: int, q_offset: int,
):
    ki, g, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(g == 0, qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos, kpos = _positions(qi, ki, bq, bk, q_offset)
        s = jnp.where(_tile_mask(qpos, kpos, causal, window), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])  # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(  # p^T @ do -> (bk, d)
            p, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[...] += jax.lax.dot_general(  # ds^T @ q -> (bk, d)
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    rel = _tile_relevant(qi, ki, bq, bk, q_offset, causal, window)
    if rel is None:
        _compute()
    else:
        pl.when(rel)(_compute)

    @pl.when(jnp.logical_and(g == G - 1, qi == nq - 1))
    def _write():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _tiling(Sq: int, Sk: int, blocks: Tuple[int, int]):
    """Static tile heuristic (the autotuner's cache-miss fallback): largest
    sublane-aligned divisors <= the requested blocks via the shared
    ``_pick``, replacing the old power-of-two halving loop that could land
    on needlessly small tiles for non-power-of-two sequence lengths."""
    from repro.kernels.expert_gemm import _pick

    bq = _pick(blocks[0], Sq, align=8)
    bk = _pick(blocks[1], Sk, align=8)
    return bq, bk


def _fa_call(q, k, v, causal, window, scale, blocks, interpret):
    """Shared forward: returns (out in the public layout, lse (B*H, Sq, 1))."""
    B, Sq, H, d = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = _tiling(Sq, Sk, blocks)
    nq, nk = Sq // bq, Sk // bk

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, d)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, d)

    o_h, lse = pl.pallas_call(
        functools.partial(
            _fa_kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, nk=nk, q_offset=Sk - Sq,
        ),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, d), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    out = o_h.reshape(B, H, Sq, d).transpose(0, 2, 1, 3)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fa_p(q, k, v, causal, window, scale, blocks, interpret):
    out, _ = _fa_call(q, k, v, causal, window, scale, blocks, interpret)
    return out


def _fa_fwd(q, k, v, causal, window, scale, blocks, interpret):
    out, lse = _fa_call(q, k, v, causal, window, scale, blocks, interpret)
    # residuals stay in the caller's layout: q/k/v/out are alive in the
    # autodiff graph anyway, so this saves nothing extra but the lse —
    # the head-major transposes are recomputed (cheap) in the backward
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, scale, blocks, interpret, res, dout):
    q, k, v, out, lse = res
    B, Sq, H, d = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    BH, BKV, G = B * H, B * KV, H // KV
    bq, bk = _tiling(Sq, Sk, blocks)
    nq, nk = Sq // bq, Sk // bk
    q_offset = Sk - Sq

    qh = q.transpose(0, 2, 1, 3).reshape(BH, Sq, d)
    kh = k.transpose(0, 2, 1, 3).reshape(BKV, Sk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(BKV, Sk, d)
    do_h = dout.transpose(0, 2, 1, 3).reshape(BH, Sq, d)
    # softmax Jacobian diagonal, O(N*d) elementwise — no kernel needed
    delta = (
        jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
        .transpose(0, 2, 1)
        .reshape(BH, Sq, 1)
    )

    dq_h = pl.pallas_call(
        functools.partial(
            _fa_bwd_dq_kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, nk=nk, q_offset=q_offset,
        ),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), qh.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qh, kh, vh, do_h, lse, delta)

    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _fa_bwd_dkv_kernel, scale=scale, causal=causal, window=window,
            bq=bq, bk=bk, nq=nq, G=G, q_offset=q_offset,
        ),
        grid=(BKV, nk, G, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bkv, ki, g, qi, G=G: (bkv * G + g, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bkv, ki, g, qi: (bkv, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bkv, ki, g, qi: (bkv, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda bkv, ki, g, qi, G=G: (bkv * G + g, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bkv, ki, g, qi, G=G: (bkv * G + g, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bkv, ki, g, qi, G=G: (bkv * G + g, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bkv, ki, g, qi: (bkv, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bkv, ki, g, qi: (bkv, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, Sk, d), kh.dtype),
            jax.ShapeDtypeStruct((BKV, Sk, d), vh.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh, do_h, lse, delta)

    dq = dq_h.reshape(B, H, Sq, d).transpose(0, 2, 1, 3)
    dk = dk_h.reshape(B, KV, Sk, d).transpose(0, 2, 1, 3)
    dv = dv_h.reshape(B, KV, Sk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


_fa_p.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "blocks", "interpret")
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, d)
    k: jax.Array,  # (B, Sk, KV, d), H % KV == 0
    v: jax.Array,  # (B, Sk, KV, d)
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    blocks: Tuple[int, int] = DEFAULT_BLOCKS,
    interpret: bool = False,
) -> jax.Array:
    d = q.shape[-1]
    scale = float(scale) if scale is not None else d**-0.5
    return _fa_p(q, k, v, causal, window, scale, tuple(blocks), interpret)
