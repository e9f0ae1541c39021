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

* dq kernel: p = exp(s - lse), dp = do @ v^T, ds = p * (dp - delta) *
  scale, dq += ds @ k, accumulated over KV blocks in fp32 scratch.
* dk/dv kernel: same recompute per (q-block, group-head) pair; dk/dv
  accumulate over the G query heads sharing the KV head and over q blocks
  in fp32 scratch, so GQA needs no (B*H, Sk, d) staging buffer.

``delta = rowsum(do * out)`` (the softmax Jacobian diagonal) is computed
outside the kernels — it is O(N*d) elementwise.

Schedule (:func:`flash_schedule`): each kernel runs over a flat axis of the
live (q-block, k-block) tiles only — tiles with at least one unmasked entry
under the causal / window / right-aligned (``q_offset = Sk - Sq``) mask —
with the tile's block indices and flags scalar-prefetched. Forward and dq
walk the tiles q-major (initialise on a q-block's first live k-block, write
on its last); dk/dv walks them k-major with the G query heads inside
(initialise and write per k-block). Dead tiles take no grid step and no
DMA. Only the tiles that cross the diagonal or the window's edge build the
position iotas and the select; tiles wholly inside the mask run the
unmasked body. A non-causal call without a window has every tile live and
walks the full grid.

Tiles (:func:`flash_tiles`) come from the shape: the largest bq, bk up to
``MAX_BLOCK`` that divide Sq, Sk (the whole dim for short sequences),
halved while the backward's VMEM working set at that head dim — four fp32
(bq, bk) temporaries (s, p, dp, ds) beside the double-buffered blocks —
exceeds ``VMEM_BUDGET``. At S 4096 and d 64 or 128 that is (512, 512): 36
of the 64 tiles of a head are live under causal masking.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.expert_gemm import _pick

MAX_BLOCK = 512
# the autotuner's VMEM filter on the v5e profile (70% of 16 MB); the rest
# is left to the compiler's own temporaries
VMEM_BUDGET = 0.7 * 16e6
NEG_INF = -1e30

# per-step flags of a schedule entry
INIT, WRITE, FULL, EDGE = 1, 2, 4, 8  # FULL: live, unmasked; EDGE: live, masked


class FlashSchedule(NamedTuple):
    """Static tile schedule of one (Sq, Sk, bq, bk, causal, window) call.
    Walk entries are ``(qi, ki, flags)``: ``q_walk`` is the forward's and
    dq's (q-major), ``k_walk`` is dk/dv's for one query head (k-major). A
    q-block (k-block) with no live tile keeps one entry that computes
    nothing, so its output is still initialised and written."""

    nq: int
    nk: int
    q_walk: Tuple[Tuple[int, int, int], ...]
    k_walk: Tuple[Tuple[int, int, int], ...]

    @property
    def full(self) -> int:
        """Tiles of the full (nq, nk) grid."""
        return self.nq * self.nk

    @property
    def live(self) -> int:
        """Tiles with any unmasked entry: the tiles each kernel computes."""
        return sum(1 for *_, f in self.q_walk if f & (FULL | EDGE))

    @property
    def steps(self):
        """Grid steps per query head of each kernel."""
        return {"fwd": len(self.q_walk), "dq": len(self.q_walk),
                "dkv": len(self.k_walk)}


def _tile_kind(qi: int, ki: int, bq: int, bk: int, q_offset: int,
               causal: bool, window: Optional[int]) -> int:
    """FULL if every entry of the (qi, ki) tile is unmasked, EDGE if some
    are, 0 if none (a dead tile)."""
    q_lo, q_hi = qi * bq + q_offset, qi * bq + bq - 1 + q_offset
    k_lo, k_hi = ki * bk, ki * bk + bk - 1
    live = full = True
    if causal:  # kpos <= qpos
        live &= k_lo <= q_hi
        full &= k_hi <= q_lo
    if window is not None:  # kpos > qpos - window
        live &= k_hi > q_lo - window
        full &= k_lo > q_hi - window
    return FULL if full else EDGE if live else 0


def _walk(rows):
    """Flatten rows of (qi, ki, kind) tiles to their live tiles, flagging
    INIT on each row's first and WRITE on its last."""
    walk = []
    for row in rows:
        seg = [list(t) for t in row if t[2]] or [[row[0][0], row[0][1], 0]]
        seg[0][2] |= INIT
        seg[-1][2] |= WRITE
        walk += map(tuple, seg)
    return tuple(walk)


def flash_schedule(Sq: int, Sk: int, bq: int, bk: int, causal: bool,
                   window: Optional[int]) -> FlashSchedule:
    """The live-tile schedule at tiles (bq, bk), from static shapes."""
    nq, nk = Sq // bq, Sk // bk
    kind = [[_tile_kind(qi, ki, bq, bk, Sk - Sq, causal, window)
             for ki in range(nk)] for qi in range(nq)]
    return FlashSchedule(
        nq, nk,
        _walk([[(qi, ki, kind[qi][ki]) for ki in range(nk)] for qi in range(nq)]),
        _walk([[(qi, ki, kind[qi][ki]) for qi in range(nq)] for ki in range(nk)]),
    )


def _per_head(k_walk, G: int):
    """dk/dv's walk over the G query heads sharing a KV head, as
    ``(g, qi, ki, flags)``: each k-block's tiles once per head, initialised
    on the first head's first tile and written on the last head's last."""
    segs = []
    for t in k_walk:
        if t[2] & INIT:
            segs.append([])
        segs[-1].append(t)
    out = []
    for seg in segs:
        for g in range(G):
            for qi, ki, f in seg:
                if g > 0:
                    f &= ~INIT
                if g < G - 1:
                    f &= ~WRITE
                out.append((g, qi, ki, f))
    return out


def _tables(walk):
    """One int32 array per column of a walk: the scalar-prefetch tables."""
    return [jnp.asarray(np.asarray(c, np.int32)) for c in zip(*walk)]


def vmem_bytes(bq: int, bk: int, d: int, itemsize: int) -> float:
    """VMEM working set of the dk/dv kernel, the largest of the three:
    fp32 (bq, bk) s, p, dp, ds; double-buffered q, do, k, v blocks and
    lane-padded lse/delta columns; fp32 dk/dv accumulators and operand
    copies; double-buffered dk/dv output blocks."""
    return (
        4.0 * bq * bk * 4
        + 2.0 * (2 * bq + 2 * bk) * d * itemsize
        + 2.0 * 2 * bq * 128 * 4
        + 2.0 * bk * d * 4 + 2.0 * bq * d * 4
        + 2.0 * 2 * bk * d * itemsize
    )


def _tiling(Sq: int, Sk: int, blocks: Tuple[int, int]):
    """Largest sublane-aligned divisors <= the requested blocks via the
    shared ``_pick`` (the whole dim where the sequence is short)."""
    return _pick(blocks[0], Sq, align=8), _pick(blocks[1], Sk, align=8)


def flash_tiles(Sq: int, Sk: int, d: int, itemsize: int = 2):
    """(bq, bk) from the shape: the largest divisors up to ``MAX_BLOCK``,
    the larger halved while :func:`vmem_bytes` exceeds ``VMEM_BUDGET``."""
    bq, bk = _tiling(Sq, Sk, (MAX_BLOCK, MAX_BLOCK))
    while vmem_bytes(bq, bk, d, itemsize) > VMEM_BUDGET and max(bq, bk) > 8:
        if bk >= bq:
            bk = _pick(bk // 2, Sk, align=8)
        else:
            bq = _pick(bq // 2, Sq, align=8)
    return bq, bk


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


def _scores(q, k, qi, ki, masked: bool, *, scale, causal, window, bq, bk,
            q_offset):
    """fp32 (bq, bk) scaled scores of a tile; masked entries NEG_INF where
    the tile crosses an edge of the mask."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if masked:
        qpos, kpos = _positions(qi, ki, bq, bk, q_offset)
        s = jnp.where(_tile_mask(qpos, kpos, causal, window), s, NEG_INF)
    return s


def _on_live(flags, compute):
    """Run ``compute(masked)`` on a live tile: unmasked where the tile lies
    wholly inside the mask, masked where it crosses an edge."""
    pl.when((flags & FULL) != 0)(lambda: compute(False))
    pl.when((flags & EDGE) != 0)(lambda: compute(True))


def _fa_kernel(
    qb_ref, kb_ref, fl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr, **tile,
):
    t = pl.program_id(1)
    qi, ki, flags = qb_ref[t], kb_ref[t], fl_ref[t]

    @pl.when((flags & INIT) != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute(masked):
        s = _scores(q_ref[0], k_ref[0], qi, ki, masked, **tile)  # (bq, bk)
        m_prev, l_prev = m_scr[...], l_scr[...]  # (bq, 1) columns
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
        )

    _on_live(flags, _compute)

    @pl.when((flags & WRITE) != 0)
    def _write():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _fa_bwd_dq_kernel(
    qb_ref, kb_ref, fl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_acc, **tile,
):
    t = pl.program_id(1)
    qi, ki, flags = qb_ref[t], kb_ref[t], fl_ref[t]

    @pl.when((flags & INIT) != 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute(masked):
        k, v, do = k_ref[0], v_ref[0], do_ref[0]
        s = _scores(q_ref[0], k, qi, ki, masked, **tile)
        p = jnp.exp(s - lse_ref[0])  # (bq, bk), masked entries -> 0
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * tile["scale"]
        dq_acc[...] += jnp.dot(
            ds, k.astype(jnp.float32), preferred_element_type=jnp.float32
        )

    _on_live(flags, _compute)

    @pl.when((flags & WRITE) != 0)
    def _write():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(
    hg_ref, qb_ref, kb_ref, fl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, **tile,
):
    t = pl.program_id(1)
    qi, ki, flags = qb_ref[t], kb_ref[t], fl_ref[t]

    @pl.when((flags & INIT) != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        q, v, do = q_ref[0], v_ref[0], do_ref[0]
        s = _scores(q, k_ref[0], qi, ki, masked, **tile)
        p = jnp.exp(s - lse_ref[0])  # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(  # p^T @ do -> (bk, d)
            p, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * tile["scale"]
        dk_acc[...] += jax.lax.dot_general(  # ds^T @ q -> (bk, d)
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _on_live(flags, _compute)

    @pl.when((flags & WRITE) != 0)
    def _write():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_call(q, k, v, causal, window, scale, blocks, interpret):
    """Shared forward: returns (out in the public layout, lse (B*H, Sq, 1))."""
    B, Sq, H, d = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = blocks
    sched = flash_schedule(Sq, Sk, bq, bk, causal, window)
    tile = dict(scale=scale, causal=causal, window=window, bq=bq, bk=bk,
                q_offset=Sk - Sq)

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, d)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, d)

    q_map = lambda bh, t, qb, kb, fl: (bh, qb[t], 0)  # noqa: E731
    kv_map = lambda bh, t, qb, kb, fl: (bh // G, kb[t], 0)  # noqa: E731
    o_h, lse = pl.pallas_call(
        functools.partial(_fa_kernel, **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B * H, len(sched.q_walk)),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bk, d), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, bq, 1), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, d), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*_tables(sched.q_walk), qh, kh, vh)
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
    bq, bk = blocks
    sched = flash_schedule(Sq, Sk, bq, bk, causal, window)
    tile = dict(scale=scale, causal=causal, window=window, bq=bq, bk=bk,
                q_offset=Sk - Sq)

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

    q_map = lambda bh, t, qb, kb, fl: (bh, qb[t], 0)  # noqa: E731
    kv_map = lambda bh, t, qb, kb, fl: (bh // G, kb[t], 0)  # noqa: E731
    dq_h = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(BH, len(sched.q_walk)),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, bq, 1), q_map),
                pl.BlockSpec((1, bq, 1), q_map),
            ],
            out_specs=pl.BlockSpec((1, bq, d), q_map),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), qh.dtype),
        interpret=interpret,
    )(*_tables(sched.q_walk), qh, kh, vh, do_h, lse, delta)

    dkv_walk = _per_head(sched.k_walk, G)
    hq_map = lambda b, t, hg, qb, kb, fl: (b * G + hg[t], qb[t], 0)  # noqa: E731
    k_map = lambda b, t, hg, qb, kb, fl: (b, kb[t], 0)  # noqa: E731
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(BKV, len(dkv_walk)),
            in_specs=[
                pl.BlockSpec((1, bq, d), hq_map),
                pl.BlockSpec((1, bk, d), k_map),
                pl.BlockSpec((1, bk, d), k_map),
                pl.BlockSpec((1, bq, d), hq_map),
                pl.BlockSpec((1, bq, 1), hq_map),
                pl.BlockSpec((1, bq, 1), hq_map),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), k_map),
                pl.BlockSpec((1, bk, d), k_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BKV, Sk, d), kh.dtype),
            jax.ShapeDtypeStruct((BKV, Sk, d), vh.dtype),
        ],
        interpret=interpret,
    )(*_tables(dkv_walk), qh, kh, vh, do_h, lse, delta)

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
    blocks: Optional[Tuple[int, int]] = None,
    interpret: bool = False,
) -> jax.Array:
    """``blocks`` bounds the tiles (each cut to a divisor of its sequence);
    None takes :func:`flash_tiles` of the shape."""
    Sq, d = q.shape[1], q.shape[-1]
    scale = float(scale) if scale is not None else d**-0.5
    tiles = (flash_tiles(Sq, k.shape[1], d, q.dtype.itemsize) if blocks is None
             else _tiling(Sq, k.shape[1], tuple(blocks)))
    return _fa_p(q, k, v, causal, window, scale, tiles, interpret)
