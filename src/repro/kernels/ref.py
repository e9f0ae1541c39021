"""Pure-jnp oracles for the Pallas kernels. Each kernel's tests sweep
shapes/dtypes and assert_allclose against these."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def expert_gemm_ref(
    xe: jax.Array,  # (E, C, D) tokens per expert
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
) -> jax.Array:
    """Fused SwiGLU expert FFN: silu(x@wg) * (x@wu) @ wd, batched over E."""
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate, preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", xe, w_up, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(xe.dtype)
    return jnp.einsum(
        "ecf,efd->ecd", h, w_down, preferred_element_type=jnp.float32
    ).astype(xe.dtype)


def grouped_gemm_ref(
    xs: jax.Array,  # (N, D) expert-sorted rows (may be tile-align padded)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    group_sizes: jax.Array,  # (E,) valid rows per expert
    row_block: int = 1,
) -> jax.Array:
    """Group-size-aware fused SwiGLU FFN over the flat expert-sorted layout.
    Each expert's region starts at its (row_block-aligned) offset; rows past
    ``group_sizes[e]`` produce zeros. O(E) python loop — oracle only."""
    N, D = xs.shape
    E = w_gate.shape[0]
    b = row_block
    padded = ((group_sizes + b - 1) // b) * b
    starts = jnp.cumsum(padded) - padded
    row = jnp.arange(N)
    out = jnp.zeros((N, w_down.shape[-1]), jnp.float32)
    for e in range(E):
        g = jnp.dot(xs, w_gate[e], preferred_element_type=jnp.float32)
        u = jnp.dot(xs, w_up[e], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(xs.dtype)
        y = jnp.dot(h, w_down[e], preferred_element_type=jnp.float32)
        mine = (row >= starts[e]) & (row < starts[e] + group_sizes[e])
        out = jnp.where(mine[:, None], y, out)
    return out.astype(xs.dtype)


def expert_gemm_q8_ref(
    xe: jax.Array,  # (E, C, D)
    w_gate: jax.Array,  # (E, D, F) int8
    w_up: jax.Array,  # (E, D, F) int8
    w_down: jax.Array,  # (E, F, D) int8
    s_gate: jax.Array,  # (E, F) per-output-channel scales
    s_up: jax.Array,  # (E, F)
    s_down: jax.Array,  # (E, D)
) -> jax.Array:
    """Oracle for the fused-dequant int8 expert FFN: int8 weights cast to
    the activation dtype for the matmul (exact — |q| <= 127), fp32
    accumulate, scale applied to the accumulator (per-output-channel
    scales commute with the contraction). Mirrors the kernel math."""
    wdt = xe.dtype
    g = jnp.einsum(
        "ecd,edf->ecf", xe, w_gate.astype(wdt), preferred_element_type=jnp.float32
    ) * s_gate[:, None, :].astype(jnp.float32)
    u = jnp.einsum(
        "ecd,edf->ecf", xe, w_up.astype(wdt), preferred_element_type=jnp.float32
    ) * s_up[:, None, :].astype(jnp.float32)
    h = (jax.nn.silu(g) * u).astype(xe.dtype)
    y = jnp.einsum(
        "ecf,efd->ecd", h, w_down.astype(wdt), preferred_element_type=jnp.float32
    ) * s_down[:, None, :].astype(jnp.float32)
    return y.astype(xe.dtype)


def grouped_gemm_q8_ref(
    xs: jax.Array,  # (N, D) expert-sorted rows (may be tile-align padded)
    w_gate: jax.Array,  # (E, D, F) int8
    w_up: jax.Array,  # (E, D, F) int8
    w_down: jax.Array,  # (E, F, D) int8
    s_gate: jax.Array,  # (E, F)
    s_up: jax.Array,  # (E, F)
    s_down: jax.Array,  # (E, D)
    group_sizes: jax.Array,  # (E,) valid rows per expert
    row_block: int = 1,
) -> jax.Array:
    """int8 grouped-GEMM oracle over the sorted layout; same region/mask
    logic as :func:`grouped_gemm_ref`, kernel-mirroring dequant math."""
    N, D = xs.shape
    E = w_gate.shape[0]
    b = row_block
    padded = ((group_sizes + b - 1) // b) * b
    starts = jnp.cumsum(padded) - padded
    row = jnp.arange(N)
    out = jnp.zeros((N, w_down.shape[-1]), jnp.float32)
    wdt = xs.dtype
    for e in range(E):
        g = jnp.dot(
            xs, w_gate[e].astype(wdt), preferred_element_type=jnp.float32
        ) * s_gate[e].astype(jnp.float32)
        u = jnp.dot(
            xs, w_up[e].astype(wdt), preferred_element_type=jnp.float32
        ) * s_up[e].astype(jnp.float32)
        h = (jax.nn.silu(g) * u).astype(xs.dtype)
        y = jnp.dot(
            h, w_down[e].astype(wdt), preferred_element_type=jnp.float32
        ) * s_down[e].astype(jnp.float32)
        mine = (row >= starts[e]) & (row < starts[e] + group_sizes[e])
        out = jnp.where(mine[:, None], y, out)
    return out.astype(xs.dtype)


def paged_attention_ref(
    q: jax.Array,  # (B, H, d) one query token per sequence
    k_pool: jax.Array,  # (num_pages, KV, page_size, d) shared page pool
    v_pool: jax.Array,  # (num_pages, KV, page_size, d)
    block_table: jax.Array,  # (B, max_pages) int32 page ids, -1 = unassigned
    seq_lens: jax.Array,  # (B,) int32 tokens valid per sequence (incl. current)
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """XLA gather oracle for the Pallas paged-attention decode kernel.

    Logical KV slot ``j`` of sequence ``b`` and KV head ``h`` lives at
    ``pool[block_table[b, j // page_size], h, j % page_size]`` (identity
    position mapping — pages never wrap). Slots with ``j >= seq_lens[b]``
    or an unassigned page are masked. Returns (B, H, d)."""
    B, H, d = q.shape
    _, KV, ps, _ = k_pool.shape
    G = H // KV
    scale = scale if scale is not None else d**-0.5
    bt = jnp.maximum(block_table, 0)
    # (B, maxP, KV, ps, d) -> token-major (B, maxP*ps, KV, d)
    kg = k_pool[bt].transpose(0, 1, 3, 2, 4).reshape(B, -1, KV, d)
    vg = v_pool[bt].transpose(0, 1, 3, 2, 4).reshape(B, -1, KV, d)
    S = kg.shape[1]
    kpos = jnp.arange(S, dtype=jnp.int32)[None, :]
    valid = (kpos < seq_lens[:, None]) & (block_table >= 0)[
        :, jnp.arange(S) // ps
    ]
    if window is not None:
        valid &= kpos > (seq_lens[:, None] - 1) - window
    qg = q.reshape(B, KV, G, d)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, kg, preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgs,bskd->bkgd", p.astype(vg.dtype), vg, preferred_element_type=jnp.float32
    )
    # fully-masked sequences (e.g. an idle batch slot) emit zeros, not the
    # uniform-softmax average of garbage — keeps the kernel parity exact
    out = jnp.where(valid.any(-1)[:, None, None, None], out, 0.0)
    return out.reshape(B, H, d).astype(v_pool.dtype)


def paged_attention_q8_ref(
    q: jax.Array,  # (B, H, d)
    k_pool: jax.Array,  # (num_pages, KV, page_size, d) int8
    v_pool: jax.Array,  # (num_pages, KV, page_size, d) int8
    k_scale: jax.Array,  # (num_pages, KV, page_size, 1) per-token scales
    v_scale: jax.Array,  # (num_pages, KV, page_size, 1)
    block_table: jax.Array,  # (B, max_pages) int32 page ids, -1 = unassigned
    seq_lens: jax.Array,  # (B,) int32
    window=None,
    scale=None,
) -> jax.Array:
    """int8-KV oracle: dequantize the pools (per-token, per-kv-head
    sidecar scales) in f32 and run the bf16 paged-attention oracle on the
    result. Returns q.dtype."""
    kd = k_pool.astype(jnp.float32) * k_scale.astype(jnp.float32)
    vd = v_pool.astype(jnp.float32) * v_scale.astype(jnp.float32)
    out = paged_attention_ref(
        q.astype(jnp.float32), kd, vd, block_table, seq_lens,
        window=window, scale=scale,
    )
    return out.astype(q.dtype)


def flash_attention_ref(
    q: jax.Array,  # (B, Sq, H, d)
    k: jax.Array,  # (B, Sk, H, d)  (kv heads pre-broadcast to H)
    v: jax.Array,  # (B, Sk, H, d)
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    B, Sq, H, d = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else d**-0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    qp = jnp.arange(Sq)[:, None] + (Sk - Sq)  # right-aligned positions
    kp = jnp.arange(Sk)[None, :]
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= kp > qp - window
    s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    ).astype(v.dtype)
