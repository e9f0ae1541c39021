"""Pallas TPU grouped expert GEMM with fused SwiGLU epilogue.

The MoE expert FFN is the paper's dominant compute hot-spot (it is what the
46.8%-MFU engineering in Table 2 is about). On H100 Megatron uses a CUTLASS
grouped GEMM; the TPU adaptation re-tiles for the MXU and the HBM->VMEM
hierarchy. Two layouts, matching the two dispatcher families
(core/dispatch/):

Padded layout (``expert_gemm``, allgather/alltoall dispatchers): dense
(E, C, D) buffer, one grid slice per expert.

* kernel 1 (``gate_up``): h = silu(x @ w_gate) * (x @ w_up). Both gemms
  share the same x tile (one HBM read), accumulate in fp32 VMEM scratch over
  the D-contraction grid dim, and the SwiGLU epilogue runs in VMEM — the
  (E,C,F) gate/up intermediates NEVER round-trip to HBM (the fusion win:
  saves 2*E*C*F bf16 writes + reads per layer vs. the XLA path).
* kernel 2 (``down``): y = h @ w_down, a plain k-blocked grouped matmul.

Sorted layout (``grouped_gemm``, sorted dropless dispatcher): flat (N, D)
expert-sorted buffer with per-expert ``group_sizes``, each expert's region
aligned to the row-tile size. Per-row-tile expert ids and valid-row counts
are scalar-prefetched (PrefetchScalarGridSpec) so each tile loads exactly
its expert's weight block; rows past the expert's count are masked in the
epilogue and fully-empty tiles skip the MXU work entirely — the
group-size-aware part that makes dropless cost scale with T*k instead of
E*C. fp32 accumulation and the fused SwiGLU epilogue are identical to the
padded kernels.

Tiles default to (bc, bf, bd) = (128, 512, 512) — MXU-aligned multiples of
128, VMEM footprint ~= bc*bd + 2*bd*bf + 2*bc*bf(fp32) ~= 3.3 MB at bf16.
Expert-parallel composition: the kernel sees the *local* expert shard
(E_loc, ...); dispatch/combine collectives live a level up in
core/dispatch/.

Both entry points are differentiable: ``expert_gemm`` and ``grouped_gemm``
carry a ``jax.custom_vjp`` whose backward pass is three more Pallas grouped
kernels over the same scalar-prefetched per-tile expert-id machinery:

* dgrad 1 (``_grouped_bwd_dh_kernel``): recomputes the gate/up projections
  from ``x`` (one extra D-contraction pass), fuses ``dh = dy @ w_down^T``
  into the same grid, and applies the SwiGLU backward in the epilogue —
  emitting ``h``, ``dg``, ``du`` as *backward-transient* buffers.
* dgrad 2 (``_grouped_bwd_dx_kernel``): ``dx = dg @ w_gate^T + du @
  w_up^T``, one fused k-blocked pass over F.
* wgrad (``_grouped_bwd_wgrad_kernel``): ``dw_gate[e] = x_e^T @ dg_e`` etc.
  over the transposed ragged layout — row tiles are the *minor* grid dim so
  each expert's fp32 output block is revisited consecutively and
  accumulated in VMEM, initialized on expert change (group boundaries are
  contiguous in the sorted layout by construction).

Because the backward RECOMPUTES the SwiGLU intermediates, the forward saves
only ``(x, weights, group_sizes)`` as residuals: activation memory per MoE
layer drops from O(N*F) (gate/up/h saved by autodiff) to O(N*D). The padded
``expert_gemm`` backward reuses the grouped kernels by viewing ``(E, C, D)``
as an exactly-tile-aligned sorted buffer with ``group_sizes == C``.

Validated in interpret mode against kernels/ref.py over shape/dtype sweeps,
forward and backward (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCKS = (128, 512, 512)  # (bc, bf, bd)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _gate_up_kernel(x_ref, wg_ref, wu_ref, h_ref, g_acc, u_acc, *, nd: int):
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        u_acc[...] = jnp.zeros_like(u_acc)

    x = x_ref[0]
    g_acc[...] += jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    u_acc[...] += jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)

    @pl.when(d == nd - 1)
    def _epilogue():
        h_ref[0] = (_silu(g_acc[...]) * u_acc[...]).astype(h_ref.dtype)


def _down_kernel(h_ref, wd_ref, y_ref, acc, *, nf: int):
    f = pl.program_id(3)

    @pl.when(f == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(h_ref[0], wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _write():
        y_ref[0] = acc[...].astype(y_ref.dtype)


def _pick(block: int, dim: int, align: int = 128, itemsize: int = 2) -> int:
    """Largest tile <= ``block`` that divides ``dim``, ``align``-aligned.

    ``align=128`` (lane dims F/D): the tile is the largest multiple-of-128
    divisor (the old halving loop could land on lane-misaligned sizes like
    96 or 192 for non-power-of-two dims); dims with no such divisor are
    only legal as a single whole-dim tile (the compiler pads it), so any
    smaller split asserts. ``align=8`` (the sublane/row dim C): prefer a
    multiple-of-8 tile but fall back to the largest divisor — arbitrary
    capacities (e.g. C=282 from a CF ceil) stay legal as they always were.

    ``itemsize`` is the element byte width of the tensor streamed along
    this dim; the ``block`` budget is calibrated in bf16-equivalent
    elements (itemsize=2), so int8 operands (itemsize=1) get twice the
    rows at the same VMEM byte budget and f32 half. The scaled tile goes
    through the same divisor search, so lane alignment still holds (the
    ``align>=128`` assert below fires on any misaligned split).
    """
    assert itemsize in (1, 2, 4), itemsize
    block = max(1, (block * 2) // itemsize)
    b = min(block, dim)
    for cand in range(b - b % align, 0, -align):
        if dim % cand == 0:
            return cand
    if align >= 128:
        # lane dims: a misaligned tile is only safe when it spans the whole
        # (compiler-padded) dim; any other split straddles lane boundaries
        assert b == dim, (
            f"no {align}-aligned tile <= {block} divides {dim}; pad the dim "
            f"to a multiple of {align} or use a whole-dim block"
        )
        return b
    # row/sublane dim: the compiler pads sublanes, so any divisor is legal
    # (arbitrary capacities like C=282 must not crash) — take the largest
    for cand in range(b, 0, -1):
        if dim % cand == 0:
            return cand
    return 1


def _dot_nt(a, b):
    """(m, k) x (n, k) -> (m, n): contract the last dims (B^T without an
    explicit in-VMEM transpose)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tn(a, b):
    """(k, m) x (k, n) -> (m, n): contract the first (row) dims."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _expert_fwd_impl(
    xe: jax.Array,  # (E, C, D)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    blocks: Tuple[int, int, int],
    interpret: bool,
) -> jax.Array:
    E, C, D = xe.shape
    F = w_gate.shape[-1]
    bc = _pick(blocks[0], C, align=8)  # row dim: sublane alignment suffices
    bf, bd = (_pick(b, d) for b, d in zip(blocks[1:], (F, D)))
    nc, nf, nd = C // bc, F // bf, D // bd

    h = pl.pallas_call(
        functools.partial(_gate_up_kernel, nd=nd),
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, c, f, d: (e, c, d)),
            pl.BlockSpec((1, bd, bf), lambda e, c, f, d: (e, d, f)),
            pl.BlockSpec((1, bd, bf), lambda e, c, f, d: (e, d, f)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, c, f, d: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), xe.dtype),
        scratch_shapes=[
            pltpu.VMEM((bc, bf), jnp.float32),
            pltpu.VMEM((bc, bf), jnp.float32),
        ],
        interpret=interpret,
    )(xe, w_gate, w_up)

    y = pl.pallas_call(
        functools.partial(_down_kernel, nf=nf),
        grid=(E, nc, nd, nf),
        in_specs=[
            pl.BlockSpec((1, bc, bf), lambda e, c, d, f: (e, c, f)),
            pl.BlockSpec((1, bf, bd), lambda e, c, d, f: (e, f, d)),
        ],
        out_specs=pl.BlockSpec((1, bc, bd), lambda e, c, d, f: (e, c, d)),
        out_shape=jax.ShapeDtypeStruct((E, C, D), xe.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bd), jnp.float32)],
        interpret=interpret,
    )(h, w_down)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _expert_gemm_p(xe, w_gate, w_up, w_down, blocks, interpret):
    return _expert_fwd_impl(xe, w_gate, w_up, w_down, blocks, interpret)


def _expert_gemm_fwd(xe, w_gate, w_up, w_down, blocks, interpret):
    y = _expert_fwd_impl(xe, w_gate, w_up, w_down, blocks, interpret)
    # recompute contract: no (E, C, F) SwiGLU intermediate is saved
    return y, (xe, w_gate, w_up, w_down)


def _expert_gemm_bwd(blocks, interpret, res, dy):
    xe, w_gate, w_up, w_down = res
    E, C, D = xe.shape
    # the dense padded buffer IS an exactly-tile-aligned sorted buffer with
    # group_sizes == C; reuse the grouped backward kernels on the flat view
    bc = _pick(blocks[0], C, align=8)
    gs = jnp.full((E,), C, jnp.int32)
    dxs, dwg, dwu, dwd = _grouped_bwd_impl(
        xe.reshape(E * C, D), dy.reshape(E * C, D), w_gate, w_up, w_down,
        gs, (bc,) + tuple(blocks[1:]), interpret,
    )
    return dxs.reshape(E, C, D), dwg, dwu, dwd


_expert_gemm_p.defvjp(_expert_gemm_fwd, _expert_gemm_bwd)


@functools.partial(
    jax.jit, static_argnames=("blocks", "interpret")
)
def expert_gemm(
    xe: jax.Array,  # (E, C, D)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    blocks: Tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool = False,
) -> jax.Array:
    return _expert_gemm_p(xe, w_gate, w_up, w_down, tuple(blocks), interpret)


# ---------------------------------------------------------------------------
# Group-size-aware grouped GEMM over the flat expert-sorted layout
# ---------------------------------------------------------------------------


def _grouped_gate_up_kernel(
    tg_ref, tr_ref, x_ref, wg_ref, wu_ref, h_ref, g_acc, u_acc, *, nd: int,
    bc: int, bf: int,
):
    t, d = pl.program_id(0), pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        u_acc[...] = jnp.zeros_like(u_acc)

    valid = tr_ref[t]

    @pl.when(valid > 0)  # fully-empty tiles (group padding) skip the MXU
    def _compute():
        x = x_ref[...]
        g_acc[...] += jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u_acc[...] += jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)

    @pl.when(d == nd - 1)
    def _epilogue():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bc, bf), 0)
        h = _silu(g_acc[...]) * u_acc[...]
        h_ref[...] = jnp.where(rows < valid, h, 0.0).astype(h_ref.dtype)


def _grouped_down_kernel(
    tg_ref, tr_ref, h_ref, wd_ref, y_ref, acc, *, nf: int, bc: int, bd: int
):
    t, f = pl.program_id(0), pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    valid = tr_ref[t]

    @pl.when(valid > 0)
    def _compute():
        acc[...] += jnp.dot(h_ref[...], wd_ref[0], preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _write():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bc, bd), 0)
        y_ref[...] = jnp.where(rows < valid, acc[...], 0.0).astype(y_ref.dtype)


def group_tiling(group_sizes: jax.Array, num_tiles: int, bc: int):
    """Per-row-tile metadata for the tile-aligned expert-sorted buffer:
    (tile_group (nt,) expert id, tile_rows (nt,) valid rows in [0, bc]).
    Tiles past the last group get tile_rows 0 (skipped + masked)."""
    E = group_sizes.shape[0]
    padded = ((group_sizes + bc - 1) // bc) * bc
    ends_pad = jnp.cumsum(padded)
    starts_pad = ends_pad - padded
    tile_start = jnp.arange(num_tiles, dtype=jnp.int32) * bc
    tg = jnp.searchsorted(ends_pad, tile_start, side="right")
    tg = jnp.clip(tg, 0, E - 1).astype(jnp.int32)
    tr = jnp.clip(group_sizes[tg] - (tile_start - starts_pad[tg]), 0, bc)
    return tg, tr.astype(jnp.int32)


def _grouped_fwd_impl(
    xs: jax.Array,  # (N_pad, D) expert-sorted rows, groups row-tile aligned
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    group_sizes: jax.Array,  # (E,) int32 valid rows per expert
    blocks: Tuple[int, int, int],
    interpret: bool,
) -> jax.Array:
    N_pad, D = xs.shape
    E, _, F = w_gate.shape
    bc = blocks[0]
    assert N_pad % bc == 0, (N_pad, bc)
    bf, bd = (_pick(b, d) for b, d in zip(blocks[1:], (F, D)))
    nt, nf, nd = N_pad // bc, F // bf, D // bd
    tg, tr = group_tiling(group_sizes, nt, bc)

    h = pl.pallas_call(
        functools.partial(_grouped_gate_up_kernel, nd=nd, bc=bc, bf=bf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, nf, nd),
            in_specs=[
                pl.BlockSpec((bc, bd), lambda t, f, d, tg, tr: (t, d)),
                pl.BlockSpec((1, bd, bf), lambda t, f, d, tg, tr: (tg[t], d, f)),
                pl.BlockSpec((1, bd, bf), lambda t, f, d, tg, tr: (tg[t], d, f)),
            ],
            out_specs=pl.BlockSpec((bc, bf), lambda t, f, d, tg, tr: (t, f)),
            scratch_shapes=[
                pltpu.VMEM((bc, bf), jnp.float32),
                pltpu.VMEM((bc, bf), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N_pad, F), xs.dtype),
        interpret=interpret,
    )(tg, tr, xs, w_gate, w_up)

    y = pl.pallas_call(
        functools.partial(_grouped_down_kernel, nf=nf, bc=bc, bd=bd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, nd, nf),
            in_specs=[
                pl.BlockSpec((bc, bf), lambda t, d, f, tg, tr: (t, f)),
                pl.BlockSpec((1, bf, bd), lambda t, d, f, tg, tr: (tg[t], f, d)),
            ],
            out_specs=pl.BlockSpec((bc, bd), lambda t, d, f, tg, tr: (t, d)),
            scratch_shapes=[pltpu.VMEM((bc, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((N_pad, D), xs.dtype),
        interpret=interpret,
    )(tg, tr, h, w_down)
    return y


# ---------------------------------------------------------------------------
# Backward grouped kernels (shared by grouped_gemm and expert_gemm VJPs)
# ---------------------------------------------------------------------------


def _grouped_bwd_dh_kernel(
    tg_ref, tr_ref, x_ref, dy_ref, wg_ref, wu_ref, wd_ref,
    h_ref, dg_ref, du_ref, g_acc, u_acc, dh_acc, *, nd: int, bc: int, bf: int,
):
    """Pass 1: recompute gate/up from x and fuse dh = dy @ w_down^T into the
    same D-contraction grid; the epilogue applies the SwiGLU backward.
    Emits h (for the down wgrad), dg, du — backward transients, never
    forward residuals."""
    t, d = pl.program_id(0), pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        u_acc[...] = jnp.zeros_like(u_acc)
        dh_acc[...] = jnp.zeros_like(dh_acc)

    valid = tr_ref[t]

    @pl.when(valid > 0)
    def _compute():
        x = x_ref[...]
        g_acc[...] += jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u_acc[...] += jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        dh_acc[...] += _dot_nt(dy_ref[...], wd_ref[0])

    @pl.when(d == nd - 1)
    def _epilogue():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bc, bf), 0)
        keep = rows < valid
        g, u, dh = g_acc[...], u_acc[...], dh_acc[...]
        sig = jax.nn.sigmoid(g)
        silu = g * sig
        dsilu = sig * (1.0 + g * (1.0 - sig))
        h_ref[...] = jnp.where(keep, silu * u, 0.0).astype(h_ref.dtype)
        dg_ref[...] = jnp.where(keep, dh * u * dsilu, 0.0).astype(dg_ref.dtype)
        du_ref[...] = jnp.where(keep, dh * silu, 0.0).astype(du_ref.dtype)


def _grouped_bwd_dx_kernel(
    tg_ref, tr_ref, dg_ref, du_ref, wg_ref, wu_ref, dx_ref, acc,
    *, nf: int, bc: int, bd: int,
):
    """Pass 2: dx = dg @ w_gate^T + du @ w_up^T, fused F-contraction."""
    t, f = pl.program_id(0), pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    valid = tr_ref[t]

    @pl.when(valid > 0)
    def _compute():
        acc[...] += _dot_nt(dg_ref[...], wg_ref[0])
        acc[...] += _dot_nt(du_ref[...], wu_ref[0])

    @pl.when(f == nf - 1)
    def _write():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bc, bd), 0)
        dx_ref[...] = jnp.where(rows < valid, acc[...], 0.0).astype(dx_ref.dtype)


def _grouped_bwd_wgrad_kernel(
    tg_ref, tr_ref, x_ref, dy_ref, h_ref, dg_ref, du_ref,
    dwg_ref, dwu_ref, dwd_ref,
):
    """Pass 3: wgrad over the transposed ragged layout. Row tiles are the
    minor grid dim, so each expert's fp32 output block is revisited
    consecutively; it is zero-initialized on expert change and accumulated
    in place (group regions are contiguous in t by construction). Rows past
    a group's valid count contribute nothing because dg/du/h are masked to
    zero in pass 1."""
    t = pl.program_id(2)
    tg_t = tg_ref[t]
    first = jnp.logical_or(t == 0, tg_ref[jnp.maximum(t - 1, 0)] != tg_t)

    @pl.when(first)
    def _init():
        dwg_ref[...] = jnp.zeros_like(dwg_ref)
        dwu_ref[...] = jnp.zeros_like(dwu_ref)
        dwd_ref[...] = jnp.zeros_like(dwd_ref)

    valid = tr_ref[t]

    @pl.when(valid > 0)
    def _compute():
        x, dy = x_ref[...], dy_ref[...]
        dwg_ref[0] += _dot_tn(x, dg_ref[...])   # (bd, bf)
        dwu_ref[0] += _dot_tn(x, du_ref[...])   # (bd, bf)
        dwd_ref[0] += _dot_tn(h_ref[...], dy)   # (bf, bd)


def _grouped_bwd_impl(
    xs: jax.Array,  # (N_pad, D)
    dy: jax.Array,  # (N_pad, D) cotangent of the output
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    group_sizes: jax.Array,  # (E,)
    blocks: Tuple[int, int, int],
    interpret: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    N_pad, D = xs.shape
    E, _, F = w_gate.shape
    bc = blocks[0]
    assert N_pad % bc == 0, (N_pad, bc)
    bf, bd = (_pick(b, d) for b, d in zip(blocks[1:], (F, D)))
    nt, nf, nd = N_pad // bc, F // bf, D // bd
    tg, tr = group_tiling(group_sizes, nt, bc)

    # pass 1: SwiGLU recompute + dh, one fused D-contraction grid
    h, dg, du = pl.pallas_call(
        functools.partial(_grouped_bwd_dh_kernel, nd=nd, bc=bc, bf=bf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, nf, nd),
            in_specs=[
                pl.BlockSpec((bc, bd), lambda t, f, d, tg, tr: (t, d)),
                pl.BlockSpec((bc, bd), lambda t, f, d, tg, tr: (t, d)),
                pl.BlockSpec((1, bd, bf), lambda t, f, d, tg, tr: (tg[t], d, f)),
                pl.BlockSpec((1, bd, bf), lambda t, f, d, tg, tr: (tg[t], d, f)),
                pl.BlockSpec((1, bf, bd), lambda t, f, d, tg, tr: (tg[t], f, d)),
            ],
            out_specs=[
                pl.BlockSpec((bc, bf), lambda t, f, d, tg, tr: (t, f)),
                pl.BlockSpec((bc, bf), lambda t, f, d, tg, tr: (t, f)),
                pl.BlockSpec((bc, bf), lambda t, f, d, tg, tr: (t, f)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bc, bf), jnp.float32),
                pltpu.VMEM((bc, bf), jnp.float32),
                pltpu.VMEM((bc, bf), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((N_pad, F), xs.dtype),
            jax.ShapeDtypeStruct((N_pad, F), xs.dtype),
            jax.ShapeDtypeStruct((N_pad, F), xs.dtype),
        ],
        interpret=interpret,
    )(tg, tr, xs, dy, w_gate, w_up, w_down)

    # pass 2: dx
    dx = pl.pallas_call(
        functools.partial(_grouped_bwd_dx_kernel, nf=nf, bc=bc, bd=bd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, nd, nf),
            in_specs=[
                pl.BlockSpec((bc, bf), lambda t, d, f, tg, tr: (t, f)),
                pl.BlockSpec((bc, bf), lambda t, d, f, tg, tr: (t, f)),
                pl.BlockSpec((1, bd, bf), lambda t, d, f, tg, tr: (tg[t], d, f)),
                pl.BlockSpec((1, bd, bf), lambda t, d, f, tg, tr: (tg[t], d, f)),
            ],
            out_specs=pl.BlockSpec((bc, bd), lambda t, d, f, tg, tr: (t, d)),
            scratch_shapes=[pltpu.VMEM((bc, bd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((N_pad, D), xs.dtype),
        interpret=interpret,
    )(tg, tr, dg, du, w_gate, w_up)

    # pass 3: wgrad, fp32 accumulation directly in the per-expert out blocks
    dwg, dwu, dwd = pl.pallas_call(
        _grouped_bwd_wgrad_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nd, nf, nt),
            in_specs=[
                pl.BlockSpec((bc, bd), lambda d, f, t, tg, tr: (t, d)),
                pl.BlockSpec((bc, bd), lambda d, f, t, tg, tr: (t, d)),
                pl.BlockSpec((bc, bf), lambda d, f, t, tg, tr: (t, f)),
                pl.BlockSpec((bc, bf), lambda d, f, t, tg, tr: (t, f)),
                pl.BlockSpec((bc, bf), lambda d, f, t, tg, tr: (t, f)),
            ],
            out_specs=[
                pl.BlockSpec((1, bd, bf), lambda d, f, t, tg, tr: (tg[t], d, f)),
                pl.BlockSpec((1, bd, bf), lambda d, f, t, tg, tr: (tg[t], d, f)),
                pl.BlockSpec((1, bf, bd), lambda d, f, t, tg, tr: (tg[t], f, d)),
            ],
            scratch_shapes=[],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((E, D, F), jnp.float32),
            jax.ShapeDtypeStruct((E, D, F), jnp.float32),
            jax.ShapeDtypeStruct((E, F, D), jnp.float32),
        ],
        interpret=interpret,
    )(tg, tr, xs, dy, h, dg, du)

    # experts with zero rows own no tile: their output blocks were never
    # visited (HBM garbage) and their true wgrad is zero — mask them
    live = (group_sizes > 0)[:, None, None]
    dwg = jnp.where(live, dwg, 0.0).astype(w_gate.dtype)
    dwu = jnp.where(live, dwu, 0.0).astype(w_up.dtype)
    dwd = jnp.where(live, dwd, 0.0).astype(w_down.dtype)
    return dx, dwg, dwu, dwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped_gemm_p(xs, w_gate, w_up, w_down, group_sizes, blocks, interpret):
    return _grouped_fwd_impl(xs, w_gate, w_up, w_down, group_sizes, blocks, interpret)


def _grouped_gemm_fwd(xs, w_gate, w_up, w_down, group_sizes, blocks, interpret):
    y = _grouped_fwd_impl(xs, w_gate, w_up, w_down, group_sizes, blocks, interpret)
    # recompute contract: residuals are O(N*D) inputs only — the (N, F)
    # gate/up/h intermediates are rebuilt inside the backward kernels
    return y, (xs, w_gate, w_up, w_down, group_sizes)


def _grouped_gemm_bwd(blocks, interpret, res, dy):
    xs, w_gate, w_up, w_down, group_sizes = res
    dx, dwg, dwu, dwd = _grouped_bwd_impl(
        xs, dy, w_gate, w_up, w_down, group_sizes, blocks, interpret
    )
    return dx, dwg, dwu, dwd, None  # int group_sizes: zero cotangent


_grouped_gemm_p.defvjp(_grouped_gemm_fwd, _grouped_gemm_bwd)


def grouped_gemm_residuals(xs, w_gate, w_up, w_down, group_sizes,
                           blocks: Tuple[int, int, int] = DEFAULT_BLOCKS):
    """Shape-only view of what the VJP forward saves for backward (the
    recompute contract checked by tests and the kernel bench): inputs only,
    never an (N, F) intermediate."""
    res = jax.eval_shape(
        lambda *a: _grouped_gemm_fwd(*a, tuple(blocks), True)[1],
        xs, w_gate, w_up, w_down, group_sizes,
    )
    return jax.tree.leaves(res)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def grouped_gemm(
    xs: jax.Array,  # (N_pad, D) expert-sorted rows, groups row-tile aligned
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    group_sizes: jax.Array,  # (E,) int32 valid rows per expert
    blocks: Tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool = False,
) -> jax.Array:
    return _grouped_gemm_p(
        xs, w_gate, w_up, w_down, group_sizes, tuple(blocks), interpret
    )


# ---------------------------------------------------------------------------
# int8 weights with dequant fused into the tile (serving/inference only)
#
# Weights are symmetric per-expert per-output-channel int8 (core/quant.py):
# gate/up scales over F, down over D. The scale is constant along the
# contraction dim, so dequant commutes with the matmul — the kernels load
# int8 tiles (half the HBM traffic of bf16), cast to the activation dtype
# for the MXU (int8 values are exact in bf16), accumulate in fp32, and
# multiply by the scale tile once in the epilogue. Mathematically identical
# to dequantize-then-matmul; fused SwiGLU and row masking are unchanged
# from the bf16 kernels above. The int8 contraction dim gets a 2x-rows
# weight tile at the same VMEM byte budget via _pick(itemsize=1); fp32
# accumulator tiles keep their bf16-path sizes. Forward-only: the PR 2
# custom_vjp backward kernels stay bf16.
# ---------------------------------------------------------------------------


def _scale_rows(*scales):
    """(E, N) per-channel scales -> (E, 1, N): a scale tile is then a
    ``(1, bn)`` block whose second-minor dim spans the whole (unit) axis,
    which the TPU's (8, 128) block tiling accepts; a ``(1, bn)`` row slice
    of the 2-D array is refused."""
    return tuple(s.reshape(s.shape[0], 1, s.shape[1]) for s in scales)


def _gate_up_kernel_q8(
    x_ref, wg_ref, wu_ref, sg_ref, su_ref, h_ref, g_acc, u_acc, *, nd: int
):
    d = pl.program_id(3)

    @pl.when(d == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        u_acc[...] = jnp.zeros_like(u_acc)

    x = x_ref[0]
    g_acc[...] += jnp.dot(x, wg_ref[0].astype(x.dtype), preferred_element_type=jnp.float32)
    u_acc[...] += jnp.dot(x, wu_ref[0].astype(x.dtype), preferred_element_type=jnp.float32)

    @pl.when(d == nd - 1)
    def _epilogue():
        g = g_acc[...] * sg_ref[0].astype(jnp.float32)
        u = u_acc[...] * su_ref[0].astype(jnp.float32)
        h_ref[0] = (_silu(g) * u).astype(h_ref.dtype)


def _down_kernel_q8(h_ref, wd_ref, sd_ref, y_ref, acc, *, nf: int):
    f = pl.program_id(3)

    @pl.when(f == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(
        h_ref[0], wd_ref[0].astype(h_ref.dtype), preferred_element_type=jnp.float32
    )

    @pl.when(f == nf - 1)
    def _write():
        y_ref[0] = (acc[...] * sd_ref[0].astype(jnp.float32)).astype(y_ref.dtype)


def _expert_fwd_q8_impl(
    xe: jax.Array,  # (E, C, D)
    w_gate: jax.Array,  # (E, D, F) int8
    w_up: jax.Array,  # (E, D, F) int8
    w_down: jax.Array,  # (E, F, D) int8
    s_gate: jax.Array,  # (E, F) bf16 per-output-channel scales
    s_up: jax.Array,  # (E, F)
    s_down: jax.Array,  # (E, D)
    blocks: Tuple[int, int, int],
    interpret: bool,
) -> jax.Array:
    E, C, D = xe.shape
    F = w_gate.shape[-1]
    s_gate, s_up, s_down = _scale_rows(s_gate, s_up, s_down)
    bc = _pick(blocks[0], C, align=8)
    # output tiles size the fp32 accumulators -> bf16-equivalent budget;
    # int8 contraction tiles stream 2x the rows at the same byte budget
    bf_o, bd_o = _pick(blocks[1], F), _pick(blocks[2], D)
    bd_c = _pick(blocks[2], D, itemsize=1)
    bf_c = _pick(blocks[1], F, itemsize=1)
    nc = C // bc

    h = pl.pallas_call(
        functools.partial(_gate_up_kernel_q8, nd=D // bd_c),
        grid=(E, nc, F // bf_o, D // bd_c),
        in_specs=[
            pl.BlockSpec((1, bc, bd_c), lambda e, c, f, d: (e, c, d)),
            pl.BlockSpec((1, bd_c, bf_o), lambda e, c, f, d: (e, d, f)),
            pl.BlockSpec((1, bd_c, bf_o), lambda e, c, f, d: (e, d, f)),
            pl.BlockSpec((1, 1, bf_o), lambda e, c, f, d: (e, 0, f)),
            pl.BlockSpec((1, 1, bf_o), lambda e, c, f, d: (e, 0, f)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf_o), lambda e, c, f, d: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), xe.dtype),
        scratch_shapes=[
            pltpu.VMEM((bc, bf_o), jnp.float32),
            pltpu.VMEM((bc, bf_o), jnp.float32),
        ],
        interpret=interpret,
    )(xe, w_gate, w_up, s_gate, s_up)

    y = pl.pallas_call(
        functools.partial(_down_kernel_q8, nf=F // bf_c),
        grid=(E, nc, D // bd_o, F // bf_c),
        in_specs=[
            pl.BlockSpec((1, bc, bf_c), lambda e, c, d, f: (e, c, f)),
            pl.BlockSpec((1, bf_c, bd_o), lambda e, c, d, f: (e, f, d)),
            pl.BlockSpec((1, 1, bd_o), lambda e, c, d, f: (e, 0, d)),
        ],
        out_specs=pl.BlockSpec((1, bc, bd_o), lambda e, c, d, f: (e, c, d)),
        out_shape=jax.ShapeDtypeStruct((E, C, D), xe.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bd_o), jnp.float32)],
        interpret=interpret,
    )(h, w_down, s_down)
    return y


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def expert_gemm_q8(
    xe: jax.Array,  # (E, C, D)
    w_gate: jax.Array,  # (E, D, F) int8
    w_up: jax.Array,  # (E, D, F) int8
    w_down: jax.Array,  # (E, F, D) int8
    s_gate: jax.Array,  # (E, F)
    s_up: jax.Array,  # (E, F)
    s_down: jax.Array,  # (E, D)
    blocks: Tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool = False,
) -> jax.Array:
    return _expert_fwd_q8_impl(
        xe, w_gate, w_up, w_down, s_gate, s_up, s_down, tuple(blocks), interpret
    )


def _grouped_gate_up_kernel_q8(
    tg_ref, tr_ref, x_ref, wg_ref, wu_ref, sg_ref, su_ref, h_ref, g_acc, u_acc,
    *, nd: int, bc: int, bf: int,
):
    t, d = pl.program_id(0), pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        u_acc[...] = jnp.zeros_like(u_acc)

    valid = tr_ref[t]

    @pl.when(valid > 0)
    def _compute():
        x = x_ref[...]
        g_acc[...] += jnp.dot(x, wg_ref[0].astype(x.dtype), preferred_element_type=jnp.float32)
        u_acc[...] += jnp.dot(x, wu_ref[0].astype(x.dtype), preferred_element_type=jnp.float32)

    @pl.when(d == nd - 1)
    def _epilogue():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bc, bf), 0)
        g = g_acc[...] * sg_ref[0].astype(jnp.float32)
        u = u_acc[...] * su_ref[0].astype(jnp.float32)
        h = _silu(g) * u
        h_ref[...] = jnp.where(rows < valid, h, 0.0).astype(h_ref.dtype)


def _grouped_down_kernel_q8(
    tg_ref, tr_ref, h_ref, wd_ref, sd_ref, y_ref, acc, *, nf: int, bc: int, bd: int
):
    t, f = pl.program_id(0), pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    valid = tr_ref[t]

    @pl.when(valid > 0)
    def _compute():
        acc[...] += jnp.dot(
            h_ref[...], wd_ref[0].astype(h_ref.dtype), preferred_element_type=jnp.float32
        )

    @pl.when(f == nf - 1)
    def _write():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bc, bd), 0)
        y = acc[...] * sd_ref[0].astype(jnp.float32)
        y_ref[...] = jnp.where(rows < valid, y, 0.0).astype(y_ref.dtype)


def _grouped_fwd_q8_impl(
    xs: jax.Array,  # (N_pad, D) expert-sorted rows, groups row-tile aligned
    w_gate: jax.Array,  # (E, D, F) int8
    w_up: jax.Array,  # (E, D, F) int8
    w_down: jax.Array,  # (E, F, D) int8
    s_gate: jax.Array,  # (E, F)
    s_up: jax.Array,  # (E, F)
    s_down: jax.Array,  # (E, D)
    group_sizes: jax.Array,  # (E,) int32 valid rows per expert
    blocks: Tuple[int, int, int],
    interpret: bool,
) -> jax.Array:
    N_pad, D = xs.shape
    E, _, F = w_gate.shape
    s_gate, s_up, s_down = _scale_rows(s_gate, s_up, s_down)
    bc = blocks[0]
    assert N_pad % bc == 0, (N_pad, bc)
    bf_o, bd_o = _pick(blocks[1], F), _pick(blocks[2], D)
    bd_c = _pick(blocks[2], D, itemsize=1)
    bf_c = _pick(blocks[1], F, itemsize=1)
    nt = N_pad // bc
    tg, tr = group_tiling(group_sizes, nt, bc)

    h = pl.pallas_call(
        functools.partial(
            _grouped_gate_up_kernel_q8, nd=D // bd_c, bc=bc, bf=bf_o
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, F // bf_o, D // bd_c),
            in_specs=[
                pl.BlockSpec((bc, bd_c), lambda t, f, d, tg, tr: (t, d)),
                pl.BlockSpec((1, bd_c, bf_o), lambda t, f, d, tg, tr: (tg[t], d, f)),
                pl.BlockSpec((1, bd_c, bf_o), lambda t, f, d, tg, tr: (tg[t], d, f)),
                pl.BlockSpec((1, 1, bf_o), lambda t, f, d, tg, tr: (tg[t], 0, f)),
                pl.BlockSpec((1, 1, bf_o), lambda t, f, d, tg, tr: (tg[t], 0, f)),
            ],
            out_specs=pl.BlockSpec((bc, bf_o), lambda t, f, d, tg, tr: (t, f)),
            scratch_shapes=[
                pltpu.VMEM((bc, bf_o), jnp.float32),
                pltpu.VMEM((bc, bf_o), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N_pad, F), xs.dtype),
        interpret=interpret,
    )(tg, tr, xs, w_gate, w_up, s_gate, s_up)

    y = pl.pallas_call(
        functools.partial(_grouped_down_kernel_q8, nf=F // bf_c, bc=bc, bd=bd_o),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, D // bd_o, F // bf_c),
            in_specs=[
                pl.BlockSpec((bc, bf_c), lambda t, d, f, tg, tr: (t, f)),
                pl.BlockSpec((1, bf_c, bd_o), lambda t, d, f, tg, tr: (tg[t], f, d)),
                pl.BlockSpec((1, 1, bd_o), lambda t, d, f, tg, tr: (tg[t], 0, d)),
            ],
            out_specs=pl.BlockSpec((bc, bd_o), lambda t, d, f, tg, tr: (t, d)),
            scratch_shapes=[pltpu.VMEM((bc, bd_o), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((N_pad, D), xs.dtype),
        interpret=interpret,
    )(tg, tr, h, w_down, s_down)
    return y


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def grouped_gemm_q8(
    xs: jax.Array,  # (N_pad, D) expert-sorted rows, groups row-tile aligned
    w_gate: jax.Array,  # (E, D, F) int8
    w_up: jax.Array,  # (E, D, F) int8
    w_down: jax.Array,  # (E, F, D) int8
    s_gate: jax.Array,  # (E, F)
    s_up: jax.Array,  # (E, F)
    s_down: jax.Array,  # (E, D)
    group_sizes: jax.Array,  # (E,) int32 valid rows per expert
    blocks: Tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool = False,
) -> jax.Array:
    return _grouped_fwd_q8_impl(
        xs, w_gate, w_up, w_down, s_gate, s_up, s_down, group_sizes,
        tuple(blocks), interpret,
    )


# ---------------------------------------------------------------------------
# Fused dispatch: gather + combine folded into the grouped-GEMM grid
#
# The sorted dispatcher's dispatch -> grouped_gemm -> combine pipeline costs
# two extra HBM round-trips per MoE layer: the permuted (N_pad, D) scatter
# buffer before the GEMM and the (N, D) gathered/gate-weighted output after
# it. The fused kernels absorb both, the same scalar-prefetch block-table
# trick as paged_attention:
#
# * prologue gather: the per-row token ids (``tok``) are scalar-prefetched.
#   At the first (f, d) step of row tile t the kernel DMAs the tile's valid
#   rows x[tok[t*bc + r]] of the token-major x (left in HBM, ``pl.ANY``)
#   into a VMEM row buffer, one row copy each, all in flight at once on one
#   DMA semaphore; every (f, d) step of the tile then reads its (bc, bd)
#   slice from VMEM. Each x row is read from HBM once per tile (the unfused
#   kernel re-reads its materialized buffer once per F tile), and the
#   buffer's write+read round trip disappears.
# * epilogue combine: at the last F step the down kernel scales its fp32
#   accumulator by the per-row gate, rounds to the activation dtype, and
#   DMAs each valid row straight to a slot-partials output (k*T, D) at
#   scalar-prefetched ``row_out`` = slot*T + token. Each (token, slot) pair
#   is unique in the top-k assignment list, so every partials row is
#   written exactly once — a race-free scatter with no atomics. The k slot
#   planes are summed in fp32 outside the kernel (the per-token k-way
#   combine), matching the fp32-accum convention.
# * one row per DMA: the TPU tiles the last two dims of every buffer, and
#   packs two bf16 rows into one 32-bit sublane, so a single bf16 row
#   cannot be copied. Rows travel as (rows, 1, words) of 32-bit words: f32
#   as is, and 16-bit dtypes as pairs — within each bd-wide column block,
#   column j in the low half-word and column j + bd/2 in the high one
#   (``_pack_rows``). The kernels split the contraction into the two
#   halves, so no lane shuffle is needed, and HBM traffic stays that of
#   the 16-bit data.
#
# Backward: custom_vjp with inputs-only residuals. The cotangent is pulled
# through ``jax.vjp`` of the UNFUSED composition (scatter -> grouped_gemm,
# whose own VJP recomputes SwiGLU -> gather/gate/scatter-add), so fused
# gradients agree with the unfused sorted dispatcher by construction and
# nothing O(N*F) — and no (N_pad, D) buffer — is saved across fwd/bwd.
# ---------------------------------------------------------------------------

def _lo(w):
    """Low half-words of int32 ``w`` as the f32 whose top bits they are
    (exact for bf16)."""
    return jax.lax.bitcast_convert_type(jax.lax.shift_left(w, 16), jnp.float32)


def _hi(w):
    return jax.lax.bitcast_convert_type(w & jnp.int32(-65536), jnp.float32)


def _pack(lo, hi, dtype):
    """Round f32 ``lo``/``hi`` to the 16-bit ``dtype`` and pack them into
    int32 words (``lo`` in the low half)."""
    bits = lambda a: jax.lax.bitcast_convert_type(  # noqa: E731
        a.astype(dtype).astype(jnp.float32), jnp.int32)
    return jax.lax.shift_right_logical(bits(lo), 16) | (bits(hi) & jnp.int32(-65536))


def _pack_rows(a, bd: int):
    """(R, D) 16-bit -> (R, 1, D // 2) int32 words, per bd-wide column
    block: column j in the low half-word, column j + bd/2 in the high."""
    R, D = a.shape
    b = jax.lax.bitcast_convert_type(a, jnp.uint16).astype(jnp.int32)
    b = b.reshape(R, D // bd, 2, bd // 2)
    return (b[:, :, 0] | jax.lax.shift_left(b[:, :, 1], 16)).reshape(R, 1, D // 2)


def _unpack_rows(w, bd: int):
    """Inverse of :func:`_pack_rows`, as f32 (R, D)."""
    R = w.shape[0]
    w = w.reshape(R, -1, 1, bd // 2)
    return jnp.concatenate([_lo(w), _hi(w)], axis=2).reshape(R, -1)


def _row_copies(n, copy):
    """Start ``copy(r)`` for r < n on one DMA semaphore, then wait for all
    of them (every copy moves one row, so each wait retires one)."""
    def start(r, c):
        copy(r).start()
        return c

    def wait(r, c):
        copy(0).wait()
        return c

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)


def _fused_gate_up_kernel(
    tg_ref, tr_ref, tok_ref, x_hbm, wg_ref, wu_ref, *rest,
    nd: int, bc: int, bf: int, bd: int, quant: bool, packed: bool,
):
    """Gather prologue + gate/up: grid (nt, nf, nd). At (f, d) == (0, 0)
    the tile's valid rows are DMA'd from the token-major x in HBM into
    ``x_scr``; the MXU work per (t, f, d) reads its column block from VMEM
    (as its two packed halves when ``packed``)."""
    if quant:
        sg_ref, su_ref, h_ref, x_scr, g_acc, u_acc, sem = rest
    else:
        h_ref, x_scr, g_acc, u_acc, sem = rest
    t, f, d = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    valid = tr_ref[t]

    @pl.when(jnp.logical_and(f == 0, d == 0))
    def _gather():
        _row_copies(valid, lambda r: pltpu.make_async_copy(
            x_hbm.at[tok_ref[t * bc + r]], x_scr.at[r], sem
        ))

    @pl.when(d == 0)
    def _init():
        g_acc[...] = jnp.zeros_like(g_acc)
        u_acc[...] = jnp.zeros_like(u_acc)

    @pl.when(valid > 0)
    def _compute():
        if packed:
            h2 = bd // 2
            w = x_scr[:, 0, pl.ds(pl.multiple_of(d * h2, h2), h2)]
            parts = ((_lo(w), slice(0, h2)), (_hi(w), slice(h2, bd)))
        else:
            parts = ((x_scr[:, 0, pl.ds(pl.multiple_of(d * bd, bd), bd)],
                      slice(0, bd)),)
        for x, rows in parts:
            x = x.astype(h_ref.dtype)
            g_acc[...] += jnp.dot(x, wg_ref[0, rows].astype(x.dtype),
                                  preferred_element_type=jnp.float32)
            u_acc[...] += jnp.dot(x, wu_ref[0, rows].astype(x.dtype),
                                  preferred_element_type=jnp.float32)

    @pl.when(d == nd - 1)
    def _epilogue():
        g, u = g_acc[...], u_acc[...]
        if quant:
            g = g * sg_ref[0].astype(jnp.float32)
            u = u * su_ref[0].astype(jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bc, bf), 0)
        h_ref[...] = jnp.where(rows < valid, _silu(g) * u, 0.0).astype(h_ref.dtype)


def _fused_down_kernel(
    tg_ref, tr_ref, row_ref, h_ref, wd_ref, gate_ref, *rest,
    nf: int, bc: int, bd: int, quant: bool, packed,
):
    """Down GEMM + combine epilogue: grid (nt, nd, nf). The F contraction
    accumulates in fp32; at f == nf-1 the gate-weighted (bc, bd) tile is
    staged in VMEM (packed into 32-bit words of the 16-bit ``packed``
    dtype, if given) and each valid row is DMA'd to its slot-partials row."""
    if quant:
        sd_ref, o_hbm, acc, y_scr, sem = rest
    else:
        o_hbm, acc, y_scr, sem = rest
    t, d, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    valid = tr_ref[t]

    @pl.when(f == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(valid > 0)
    def _compute():
        acc[...] += jnp.dot(
            h_ref[...], wd_ref[0].astype(h_ref.dtype),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jnp.logical_and(f == nf - 1, valid > 0))
    def _scatter():
        y = acc[...] * gate_ref[...]  # (bc, 1) f32 gate per row
        if quant:
            y = y * sd_ref[0].astype(jnp.float32)
        if packed is not None:
            h2 = bd // 2
            y_scr[:, 0, :] = _pack(y[:, :h2], y[:, h2:], packed)
            col = pl.ds(pl.multiple_of(d * h2, h2), h2)
        else:
            y_scr[:, 0, :] = y
            col = pl.ds(pl.multiple_of(d * bd, bd), bd)
        _row_copies(valid, lambda r: pltpu.make_async_copy(
            y_scr.at[r], o_hbm.at[row_ref[t * bc + r], :, col], sem
        ))


def _aligned_rows(N: int, E: int, row_block: int) -> int:
    """Static worst-case rows of the (never materialized) sorted buffer —
    mirrors core.dispatch.sorted.aligned_rows without importing the
    dispatch subsystem into the kernel layer."""
    if row_block <= 1:
        return N
    return -(-(N + E * (row_block - 1)) // row_block) * row_block


def _fused_prefetch(token, dest, slot, gate_sorted, T, N_pad):
    """Per-buffer-row vectors: source token id and slot-partials
    destination row (scalar-prefetched; padding rows are never copied) and
    the f32 gate as a (N_pad, 1) column (padding rows -> 0)."""
    tok_pad = jnp.zeros((N_pad,), jnp.int32).at[dest].set(token.astype(jnp.int32))
    row_out = jnp.zeros((N_pad,), jnp.int32).at[dest].set(
        slot.astype(jnp.int32) * T + token.astype(jnp.int32)
    )
    gate_pad = jnp.zeros((N_pad, 1), jnp.float32).at[dest, 0].set(
        gate_sorted.astype(jnp.float32)
    )
    return tok_pad, row_out, gate_pad


def _fused_fwd_impl(
    x: jax.Array,  # (T, D) token-major model activations
    w_gate: jax.Array,  # (E, D, F) (int8 when ``scales`` is given)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    scales,  # None, or (s_gate (E, F), s_up (E, F), s_down (E, D))
    group_sizes: jax.Array,  # (E,)
    token: jax.Array,  # (N,) source token per sorted row
    dest: jax.Array,  # (N,) buffer row per sorted assignment
    slot: jax.Array,  # (N,) top-k slot per sorted row (order % k)
    gate_sorted: jax.Array,  # (N,) combine gate per sorted row
    blocks: Tuple[int, int, int],
    interpret: bool,
) -> jax.Array:
    T, D = x.shape
    E, _, F = w_gate.shape
    N = token.shape[0]
    assert N % T == 0, (N, T)
    k = N // T
    quant = scales is not None
    bc = blocks[0]
    N_pad = _aligned_rows(N, E, bc)
    # output tiles size the fp32 accumulators; int8 contraction tiles
    # stream twice the rows at the same byte budget (as grouped_gemm_q8)
    bf_o, bd_o = _pick(blocks[1], F), _pick(blocks[2], D)
    bd_c = _pick(blocks[2], D, itemsize=1) if quant else bd_o
    bf_c = _pick(blocks[1], F, itemsize=1) if quant else bf_o
    nt = N_pad // bc
    tg, tr = group_tiling(group_sizes, nt, bc)
    tok_pad, row_out, gate_pad = _fused_prefetch(token, dest, slot, gate_sorted, T, N_pad)
    s_gate, s_up, s_down = _scale_rows(*scales) if quant else (None,) * 3
    # rows move as 32-bit words: 16-bit activations packed in pairs
    packed = x.dtype.itemsize == 2
    assert packed or x.dtype.itemsize == 4, x.dtype
    wpe = 2 if packed else 1  # elements per 32-bit word
    wdt = jnp.int32 if packed else x.dtype
    rows = _pack_rows(x, bd_c) if packed else x.reshape(T, 1, D)

    h = pl.pallas_call(
        functools.partial(
            _fused_gate_up_kernel, nd=D // bd_c, bc=bc, bf=bf_o, bd=bd_c,
            quant=quant, packed=packed,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nt, F // bf_o, D // bd_c),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, bd_c, bf_o), lambda t, f, d, tg, tr, tok: (tg[t], d, f)),
                pl.BlockSpec((1, bd_c, bf_o), lambda t, f, d, tg, tr, tok: (tg[t], d, f)),
            ] + ([
                pl.BlockSpec((1, 1, bf_o), lambda t, f, d, tg, tr, tok: (tg[t], 0, f)),
                pl.BlockSpec((1, 1, bf_o), lambda t, f, d, tg, tr, tok: (tg[t], 0, f)),
            ] if quant else []),
            out_specs=pl.BlockSpec((bc, bf_o), lambda t, f, d, tg, tr, tok: (t, f)),
            scratch_shapes=[
                pltpu.VMEM((bc, 1, D // wpe), wdt),
                pltpu.VMEM((bc, bf_o), jnp.float32),
                pltpu.VMEM((bc, bf_o), jnp.float32),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N_pad, F), x.dtype),
        interpret=interpret,
    )(tg, tr, tok_pad, rows, w_gate, w_up, *((s_gate, s_up) if quant else ()))

    partials = pl.pallas_call(
        functools.partial(
            _fused_down_kernel, nf=F // bf_c, bc=bc, bd=bd_o, quant=quant,
            packed=x.dtype if packed else None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nt, D // bd_o, F // bf_c),
            in_specs=[
                pl.BlockSpec((bc, bf_c), lambda t, d, f, tg, tr, ro: (t, f)),
                pl.BlockSpec((1, bf_c, bd_o), lambda t, d, f, tg, tr, ro: (tg[t], f, d)),
                pl.BlockSpec((bc, 1), lambda t, d, f, tg, tr, ro: (t, 0)),
            ] + ([
                pl.BlockSpec((1, 1, bd_o), lambda t, d, f, tg, tr, ro: (tg[t], 0, d)),
            ] if quant else []),
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((bc, bd_o), jnp.float32),
                pltpu.VMEM((bc, 1, bd_o // wpe), wdt),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((k * T, 1, D // wpe), wdt),
        interpret=interpret,
    )(tg, tr, row_out, h, w_down, gate_pad, *((s_down,) if quant else ()))

    # k-way per-token combine: fp32 sum over the slot planes, cast once
    partials = (_unpack_rows(partials, bd_o) if packed
                else partials.reshape(k * T, D).astype(jnp.float32))
    y = jnp.sum(partials.reshape(k, T, D), axis=0)
    return y.astype(x.dtype)


def _fused_unfused_ref(
    x, w_gate, w_up, w_down, group_sizes, token, dest, slot, gate_sorted,
    blocks, interpret,
):
    """The unfused sorted-dispatcher composition the fused path replaces:
    scatter into the tile-aligned buffer -> grouped_gemm (Pallas custom_vjp
    with SwiGLU recompute) -> gather + fp32 gate-weighted scatter-add. Used
    as the backward graph so fused grads match the unfused path exactly."""
    T, D = x.shape
    E = w_gate.shape[0]
    N = token.shape[0]
    N_pad = _aligned_rows(N, E, blocks[0])
    xs = jnp.zeros((N_pad, D), x.dtype).at[dest].set(x[token])
    ys = _grouped_gemm_p(xs, w_gate, w_up, w_down, group_sizes, blocks, interpret)
    yv = ys[dest].astype(jnp.float32) * gate_sorted.astype(jnp.float32)[:, None]
    return jnp.zeros((T, D), jnp.float32).at[token].add(yv).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _fused_moe_p(
    x, w_gate, w_up, w_down, group_sizes, token, dest, slot, gate_sorted,
    blocks, interpret,
):
    return _fused_fwd_impl(
        x, w_gate, w_up, w_down, None, group_sizes, token, dest, slot,
        gate_sorted, blocks, interpret,
    )


def _fused_moe_fwd(
    x, w_gate, w_up, w_down, group_sizes, token, dest, slot, gate_sorted,
    blocks, interpret,
):
    y = _fused_fwd_impl(
        x, w_gate, w_up, w_down, None, group_sizes, token, dest, slot,
        gate_sorted, blocks, interpret,
    )
    # inputs-only residuals: no (N_pad, D) buffer, no (N, F) intermediate
    return y, (x, w_gate, w_up, w_down, group_sizes, token, dest, slot, gate_sorted)


def _fused_moe_bwd(blocks, interpret, res, dy):
    x, w_gate, w_up, w_down, group_sizes, token, dest, slot, gate_sorted = res
    _, vjp = jax.vjp(
        lambda x, wg, wu, wd, g: _fused_unfused_ref(
            x, wg, wu, wd, group_sizes, token, dest, slot, g, blocks, interpret
        ),
        x, w_gate, w_up, w_down, gate_sorted,
    )
    dx, dwg, dwu, dwd, dgate = vjp(dy)
    return dx, dwg, dwu, dwd, None, None, None, None, dgate


_fused_moe_p.defvjp(_fused_moe_fwd, _fused_moe_bwd)


def fused_moe_residuals(x, w_gate, w_up, w_down, group_sizes, token, dest,
                        slot, gate_sorted,
                        blocks: Tuple[int, int, int] = DEFAULT_BLOCKS):
    """Shape-only view of the fused VJP residuals (the bench/test contract):
    token-major inputs and O(N) index vectors only — never the (N_pad, D)
    dispatch buffer or an (N, F) intermediate."""
    res = jax.eval_shape(
        lambda *a: _fused_moe_fwd(*a, tuple(blocks), True)[1],
        x, w_gate, w_up, w_down, group_sizes, token, dest, slot, gate_sorted,
    )
    return jax.tree.leaves(res)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def grouped_gemm_fused(
    x: jax.Array,  # (T, D) token-major activations (pre-dispatch)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    group_sizes: jax.Array,  # (E,) valid rows per expert
    token: jax.Array,  # (N,) source token id per sorted row (order // k)
    dest: jax.Array,  # (N,) tile-aligned buffer row per sorted row
    slot: jax.Array,  # (N,) top-k slot per sorted row (order % k)
    gate_sorted: jax.Array,  # (N,) combine gate per sorted row
    blocks: Tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool = False,
) -> jax.Array:
    """Dispatch-in-kernel sorted MoE FFN: (T, D) -> (T, D) with the token
    gather in the prologue and the gate-weighted combine in the epilogue —
    the permuted (N_pad, D) buffer and the (N, D) gathered output never
    exist in HBM. Differentiable (fused fwd, unfused-recompute bwd)."""
    return _fused_moe_p(
        x, w_gate, w_up, w_down, group_sizes, token, dest, slot, gate_sorted,
        tuple(blocks), interpret,
    )


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def grouped_gemm_fused_q8(
    x: jax.Array,  # (T, D) token-major activations (pre-dispatch)
    w_gate: jax.Array,  # (E, D, F) int8
    w_up: jax.Array,  # (E, D, F) int8
    w_down: jax.Array,  # (E, F, D) int8
    s_gate: jax.Array,  # (E, F)
    s_up: jax.Array,  # (E, F)
    s_down: jax.Array,  # (E, D)
    group_sizes: jax.Array,  # (E,)
    token: jax.Array,  # (N,)
    dest: jax.Array,  # (N,)
    slot: jax.Array,  # (N,)
    gate_sorted: jax.Array,  # (N,)
    blocks: Tuple[int, int, int] = DEFAULT_BLOCKS,
    interpret: bool = False,
) -> jax.Array:
    """int8-weight fused-dispatch sorted MoE FFN (serving): fused dequant,
    gather prologue, gate-weighted combine epilogue. Forward-only, like
    :func:`grouped_gemm_q8`."""
    return _fused_fwd_impl(
        x, w_gate, w_up, w_down, (s_gate, s_up, s_down), group_sizes,
        token, dest, slot, gate_sorted, tuple(blocks), interpret,
    )
