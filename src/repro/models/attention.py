"""Attention variants: GQA (llama/qwen/stablelm/jamba), MLA (minicpm3),
sliding-window, and decode against a ring-buffer OR paged (block-table)
KV cache.

Two compute paths:

* ``direct`` — materializes the score matrix; used for short sequences and
  single-token decode.
* ``blockwise`` — lax.scan over KV blocks with online softmax (flash-style in
  pure jnp). This is the XLA path that keeps prefill_32k / train_4k peak
  memory bounded; the Pallas ``flash_attention`` kernel (kernels/) is the
  TPU-optimized version of the same schedule and is validated against it.

Sharding: in ``tp`` mode heads shard the 'model' axis; in ``cp`` mode (head
count not divisible by the axis — see FoldingPlan) the *sequence* dim of the
attention activations shards the 'model' axis instead, the TPU analogue of
Megatron context parallelism. Decode shards the KV-cache sequence axis.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.layers import norm_apply, norm_decl, rope_apply
from repro.sharding.rules import FoldingPlan, ParamDecl

NEG_INF = -1e30
# §Perf Q2: 2048 (was 8192) — at train_4k the direct path materializes
# (B,KV,G,S,S) fp32 score chains through softmax fwd+bwd (~2 TB/step for
# qwen3); the blockwise online-softmax keeps them fusion-local.
_BLOCKWISE_MIN_SEQ = 2048
_KV_BLOCK = 1024


# ---------------------------------------------------------------------------
# Core attention math (shared by GQA and MLA-train)
# ---------------------------------------------------------------------------


def _mask(
    q_pos: jax.Array, k_pos: jax.Array, window: Optional[int], causal: bool = True
) -> jax.Array:
    """(B,Sq,Sk) validity mask: causal, windowed, and slot-valid (k_pos>=0)."""
    q = q_pos[:, :, None].astype(jnp.int32)
    k = k_pos[:, None, :].astype(jnp.int32)
    m = k >= 0
    if causal:
        m &= k <= q
    if window is not None:
        m &= k > q - window
    return m


def attention_core(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    k_pos: jax.Array,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    causal: bool = True,
    use_kernel: bool = False,
) -> jax.Array:
    """q: (B,Sq,H,dk) k: (B,Sk,KV,dk) v: (B,Sk,KV,dv); H % KV == 0.
    q_pos: (B,Sq), k_pos: (B,Sk). Returns (B,Sq,H,dv).

    ``use_kernel=True`` routes training/prefill shapes to the Pallas flash
    kernel (kernels/flash_attention.py), which is differentiable via its
    custom_vjp — the kernel assumes the contiguous right-aligned positions
    every full-sequence caller passes, so decode (ring-buffer ``k_pos``)
    and mismatched head dims fall back to the XLA paths below."""
    B, Sq, H, dk = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    dv = v.shape[-1]
    scale = scale if scale is not None else dk**-0.5
    if use_kernel and Sq == Sk and Sq > 8 and dk == dv:
        from repro.kernels.ops import flash_attention

        return flash_attention(
            q, k, v, causal=causal, window=window, scale=float(scale)
        ).astype(v.dtype)
    qg = q.reshape(B, Sq, KV, G, dk)

    # Decode (Sq small): the direct path keeps the KV cache's sequence
    # sharding intact — scores (B,KV,G,Sq,Sk) shard over Sk and the softmax
    # reduces via tiny stat all-reduces. The blockwise reshape would break
    # the Sk sharding and all-gather the entire cache every layer (§Perf D1).
    if Sq <= 8 or Sk <= _BLOCKWISE_MIN_SEQ or Sk % _KV_BLOCK != 0:
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
        ) * scale
        mask = _mask(q_pos, k_pos, window, causal)[:, None, None]
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(B, Sq, H, dv).astype(v.dtype)

    # ---- blockwise online-softmax path (flash-style, memory bounded) ----
    out = _blockwise_attention(
        qg, k, v, q_pos, k_pos,
        window if window is not None else -1, scale, causal,
    )
    return out.reshape(B, Sq, H, dv).astype(v.dtype)


def _bw_forward(qg, k, v, q_pos, k_pos, window: int, scale: float, causal: bool):
    """Online-softmax forward over KV blocks. qg: (B,Sq,KV,G,dk).
    Returns (out fp32 (B,Sq,KV,G,dv), m, l)."""
    B, Sq, KV, G, dk = qg.shape
    Sk, dv = k.shape[1], v.shape[-1]
    nb = Sk // _KV_BLOCK
    k_b = k.reshape(B, nb, _KV_BLOCK, KV, dk).transpose(1, 0, 2, 3, 4)
    v_b = v.reshape(B, nb, _KV_BLOCK, KV, dv).transpose(1, 0, 2, 3, 4)
    kp_b = k_pos.reshape(B, nb, _KV_BLOCK).transpose(1, 0, 2)

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, KV, G, dv), jnp.float32)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, kpb = xs
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, kb, preferred_element_type=jnp.float32
        ) * scale
        msk = _mask(q_pos, kpb, None if window < 0 else window, causal)[:, None, None]
        s = jnp.where(msk, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bkgqs,bskd->bqkgd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (k_b, v_b, kp_b))
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 3, 1, 2)[..., None]
    return out, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _blockwise_attention(qg, k, v, q_pos, k_pos, window: int, scale: float, causal: bool):
    out, _, _ = _bw_forward(qg, k, v, q_pos, k_pos, window, scale, causal)
    return out


def _bw_fwd(qg, k, v, q_pos, k_pos, window, scale, causal):
    out, m, l = _bw_forward(qg, k, v, q_pos, k_pos, window, scale, causal)
    return out, (qg, k, v, q_pos, k_pos, out, m, l)


def _bw_bwd(window, scale, causal, res, dout):
    """Flash-attention backward (§Perf Q3): recompute per-block
    probabilities from the saved (m, l) softmax stats — autodiff through the
    fwd scan would instead SAVE every (B,KV,G,Sq,block) probability tensor,
    forfeiting the whole memory win of the online softmax."""
    qg, k, v, q_pos, k_pos, out, m, l = res
    B, Sq, KV, G, dk = qg.shape
    Sk, dv = k.shape[1], v.shape[-1]
    nb = Sk // _KV_BLOCK
    k_b = k.reshape(B, nb, _KV_BLOCK, KV, dk).transpose(1, 0, 2, 3, 4)
    v_b = v.reshape(B, nb, _KV_BLOCK, KV, dv).transpose(1, 0, 2, 3, 4)
    kp_b = k_pos.reshape(B, nb, _KV_BLOCK).transpose(1, 0, 2)

    dout = dout.astype(jnp.float32)
    # delta[b,k,g,q] = sum_d dout * out  (the softmax Jacobian diagonal term)
    delta = jnp.einsum("bqkgd,bqkgd->bkgq", dout, out)

    def step(dq_acc, xs):
        kb, vb, kpb = xs
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, kb, preferred_element_type=jnp.float32
        ) * scale
        msk = _mask(q_pos, kpb, None if window < 0 else window, causal)[:, None, None]
        s = jnp.where(msk, s, NEG_INF)
        prob = jnp.exp(s - m[..., None]) / l[..., None]  # (B,KV,G,Sq,bk)
        dv_b = jnp.einsum(
            "bkgqs,bqkgd->bskd", prob, dout, preferred_element_type=jnp.float32
        )
        dp = jnp.einsum(
            "bqkgd,bskd->bkgqs", dout, vb.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        ds = prob * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum(
            "bkgqs,bskd->bqkgd", ds, kb.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        dk_b = jnp.einsum(
            "bkgqs,bqkgd->bskd", ds, qg.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return dq_acc, (dk_b, dv_b)

    dq0 = jnp.zeros((B, Sq, KV, G, dk), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, (k_b, v_b, kp_b))
    dk = dk_b.transpose(1, 0, 2, 3, 4).reshape(B, Sk, KV, dk).astype(k.dtype)
    dvv = dv_b.transpose(1, 0, 2, 3, 4).reshape(B, Sk, KV, dv).astype(v.dtype)
    return dq.astype(qg.dtype), dk, dvv, None, None


_blockwise_attention.defvjp(_bw_fwd, _bw_bwd)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def gqa_decl(cfg: ModelConfig) -> Dict[str, Any]:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    dt = jnp.bfloat16
    decls: Dict[str, Any] = {
        "wq": ParamDecl((D, H, hd), ("embed", "heads", "head_dim"), "fan_in", dt),
        "wk": ParamDecl((D, KV, hd), ("embed", "kv_heads", "head_dim"), "fan_in", dt),
        "wv": ParamDecl((D, KV, hd), ("embed", "kv_heads", "head_dim"), "fan_in", dt),
        "wo": ParamDecl((H, hd, D), ("heads", "head_dim", "embed"), "fan_in", dt),
    }
    if cfg.qkv_bias:
        decls["bq"] = ParamDecl((H, hd), ("heads", "head_dim"), "zeros", dt)
        decls["bk"] = ParamDecl((KV, hd), ("kv_heads", "head_dim"), "zeros", dt)
        decls["bv"] = ParamDecl((KV, hd), ("kv_heads", "head_dim"), "zeros", dt)
    return decls


def _constrain_qkv(plan: Optional[FoldingPlan], t: jax.Array, kind: str, decode: bool):
    if plan is None:
        return t
    if decode or plan.attn_mode == "tp":
        return plan.constrain(t, "fold_batch", None, kind, None)
    return plan.constrain(t, "fold_batch", "attn_seq", None, None)  # cp mode


def _gather_pages(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Token-major view of the pages a block table names: pool (pages, KV,
    ps, x) + table (B, maxP) -> (B, maxP*ps, KV, x). Unassigned (-1)
    entries read page 0; callers mask them through ``k_pos``."""
    B, maxP = page_table.shape
    _, KV, ps, x = pool.shape
    g = pool[jnp.maximum(page_table, 0)]  # (B, maxP, KV, ps, x)
    return g.transpose(0, 1, 3, 2, 4).reshape(B, maxP * ps, KV, x)


def _paged_kernel(plan: Optional[FoldingPlan], kernel, q, pools, page_table,
                  seq_lens, window):
    """Run a paged-attention Pallas kernel. On a mesh it runs per DP shard
    inside ``shard_map`` (the TPU compiler cannot partition a Mosaic
    kernel): each shard sees its own rows and its own stride of the page
    axis, so the block table's global page ids are shifted to local ones
    (-1 stays unassigned). Other mesh axes see replicated operands."""
    if plan is None:
        return kernel(q, *pools, page_table, seq_lens, window=window)
    axes = tuple(plan.batch_axes)
    part = axes or None
    local_pages = pools[0].shape[0] // math.prod(plan.mesh.shape[a] for a in axes)

    def body(q, *rest):
        *pools_l, bt, sl = rest
        off = jax.lax.axis_index(axes) * local_pages if axes else 0
        bt = jnp.where(bt >= 0, bt - off, -1)
        return kernel(q, *pools_l, bt, sl, window=window)

    return jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(P(part),) * (len(pools) + 3), out_specs=P(part),
        check_vma=False,
    )(q, *pools, page_table, seq_lens)


def gqa_apply(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    params,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_view: Optional[Dict[str, jax.Array]] = None,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,
    return_kv: bool = False,
    use_kernel: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """x: (B,S,D). ``cache``/``cache_view`` set => decode. Two cache views:
    ring (``slot``/``slot_pos``, S == 1) and paged (``page_table`` et al.,
    S >= 1 so chunked prefill shares the path — see model.paged_forward).
    ``cross_kv`` = (k, v, k_pos) precomputed encoder memory (cross-attn).
    Returns (out, updated_cache_layer)."""
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]

    if cross_kv is not None:
        k, v, k_pos = cross_kv
        out = attention_core(q, k, v, positions, k_pos, None, causal=False)
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), None

    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "bk" in params:
        k = k + params["bk"]
        v = v + params["bv"]
    q = rope_apply(q, positions, cfg.rope_theta)
    k = rope_apply(k, positions, cfg.rope_theta)

    decode = cache is not None
    q = _constrain_qkv(plan, q, "heads", decode)
    k = _constrain_qkv(plan, k, "kv_heads", decode)
    v = _constrain_qkv(plan, v, "kv_heads", decode)

    if not decode:
        out = attention_core(
            q, k, v, positions, positions,
            cfg.sliding_window if causal else None, causal=causal,
            use_kernel=use_kernel,
        )
        if return_kv:
            cache = {"k": k, "v": v}
    elif cache_view is not None and "page_table" in cache_view:
        # ---- paged decode / chunked prefill against the shared page pool --
        # cache is the pool slice for this layer: (num_pages, KV, ps, hd).
        # cache_view: page_table (B, maxP); write_page/write_offset (B, S)
        # physical scatter targets (invalid positions -> the trash page);
        # k_pos (B, maxP*ps) logical slot validity; seq_lens (B,).
        # S covers decode (1), chunked prefill, AND the speculative-decode
        # verify step (S = draft_k + 1, per-row lengths via seq_lens): the
        # gather path below is length-generic, only the S==1 Pallas decode
        # kernel is specialized. Write discipline with a prefix cache: a
        # row's table may reference *shared* (refcounted) prefix pages, but
        # wp only ever targets pages past the row's prefilled boundary —
        # the scheduler COW-clones a shared page before any chunk can
        # scatter into it, so shared KV is read-only here by construction.
        wp, wo = cache_view["write_page"], cache_view["write_offset"]
        if "k_scale" in cache:
            # int8 pages: quantize on scatter (per-token, per-kv-head
            # symmetric scales — the granularity an incremental write can
            # commit without retouching the rest of the page) and store the
            # scale in the sidecar leaf at the same (page, offset). The COW
            # discipline above covers the sidecar too: it lives in the same
            # pool subtree, so a shared page's scales are cloned with it.
            from repro.core.quant import dequantize_kv, quantize_kv

            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            k_cache = cache["k"].at[wp, :, wo].set(kq)
            v_cache = cache["v"].at[wp, :, wo].set(vq)
            ks_cache = cache["k_scale"].at[wp, :, wo].set(ks)
            vs_cache = cache["v_scale"].at[wp, :, wo].set(vs)
            if use_kernel and S == 1:
                from repro.kernels.ops import paged_attention_q8

                out = _paged_kernel(
                    plan, paged_attention_q8, q[:, 0],
                    (k_cache, v_cache, ks_cache, vs_cache),
                    cache_view["page_table"], cache_view["seq_lens"],
                    cfg.sliding_window,
                )[:, None]
            else:
                bt = cache_view["page_table"]
                kg = dequantize_kv(_gather_pages(k_cache, bt),
                                   _gather_pages(ks_cache, bt), q.dtype)
                vg = dequantize_kv(_gather_pages(v_cache, bt),
                                   _gather_pages(vs_cache, bt), q.dtype)
                out = attention_core(
                    q, kg, vg, positions, cache_view["k_pos"], cfg.sliding_window
                )
            cache = {"k": k_cache, "v": v_cache,
                     "k_scale": ks_cache, "v_scale": vs_cache}
            return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), cache
        k_cache = cache["k"].at[wp, :, wo].set(k)
        v_cache = cache["v"].at[wp, :, wo].set(v)
        if use_kernel and S == 1:
            from repro.kernels.ops import paged_attention

            out = _paged_kernel(
                plan, paged_attention, q[:, 0], (k_cache, v_cache),
                cache_view["page_table"], cache_view["seq_lens"],
                cfg.sliding_window,
            )[:, None]
        else:
            kg = _gather_pages(k_cache, cache_view["page_table"])
            vg = _gather_pages(v_cache, cache_view["page_table"])
            out = attention_core(
                q, kg, vg, positions, cache_view["k_pos"], cfg.sliding_window
            )
        cache = {"k": k_cache, "v": v_cache}
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), cache
    else:
        assert S == 1 and cache_view is not None
        slot = cache_view["slot"]  # (B,) int32 — ring-buffer write index
        k_cache = jax.vmap(lambda c, s, u: jax.lax.dynamic_update_slice(c, u, (s, 0, 0)))(
            cache["k"], slot, k
        )
        v_cache = jax.vmap(lambda c, s, u: jax.lax.dynamic_update_slice(c, u, (s, 0, 0)))(
            cache["v"], slot, v
        )
        if plan is not None:
            k_cache = plan.constrain(k_cache, "batch", "cache_seq", None, None)
            v_cache = plan.constrain(v_cache, "batch", "cache_seq", None, None)
        out = attention_core(
            q, k_cache, v_cache, positions, cache_view["slot_pos"],
            cfg.sliding_window,
        )
        cache = {"k": k_cache, "v": v_cache}
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return out, cache


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention) — MiniCPM3 / DeepSeek-V2 style
# ---------------------------------------------------------------------------


def mla_decl(cfg: ModelConfig) -> Dict[str, Any]:
    m = cfg.mla
    assert m is not None
    D, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    dt = jnp.bfloat16
    return {
        "wq_a": ParamDecl((D, m.q_lora_rank), ("embed", "lora"), "fan_in", dt),
        "q_norm": norm_decl(m.q_lora_rank),
        "wq_b": ParamDecl((m.q_lora_rank, H, qk), ("lora", "heads", "head_dim"), "fan_in", dt),
        "wkv_a": ParamDecl(
            (D, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "lora"), "fan_in", dt
        ),
        "kv_norm": norm_decl(m.kv_lora_rank),
        "wkv_b": ParamDecl(
            (m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim),
            ("lora", "heads", "head_dim"),
            "fan_in",
            dt,
        ),
        "wo": ParamDecl((H, m.v_head_dim, D), ("heads", "head_dim", "embed"), "fan_in", dt),
    }


def mla_apply(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    params,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_view: Optional[Dict[str, jax.Array]] = None,
    return_kv: bool = False,
    use_kernel: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    m = cfg.mla
    assert m is not None
    B, S, D = x.shape
    H, nope, rope_d = cfg.num_heads, m.qk_nope_head_dim, m.qk_rope_head_dim
    scale = (nope + rope_d) ** -0.5

    q_lat = norm_apply(params["q_norm"], jnp.einsum("bsd,dr->bsr", x, params["wq_a"]))
    q = jnp.einsum("bsr,rhk->bshk", q_lat, params["wq_b"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope_apply(q_rope, positions, cfg.rope_theta)

    ckv_full = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    ckv = norm_apply(params["kv_norm"], ckv_full[..., : m.kv_lora_rank])
    k_rope = rope_apply(
        ckv_full[..., m.kv_lora_rank :][:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]

    if cache is None:
        # training/prefill: expand the latent to per-head K/V (non-absorbed)
        kv = jnp.einsum("bsr,rhk->bshk", ckv, params["wkv_b"])
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rope_d))], -1
        )
        qf = jnp.concatenate([q_nope, q_rope], -1)
        if plan is not None:
            mode = "attn_seq" if plan.attn_mode == "cp" else None
            if mode:
                qf = plan.constrain(qf, "fold_batch", "attn_seq", None, None)
                k = plan.constrain(k, "fold_batch", "attn_seq", None, None)
                v = plan.constrain(v, "fold_batch", "attn_seq", None, None)
        out = attention_core(qf, k, v, positions, positions, cfg.sliding_window,
                             scale, use_kernel=use_kernel)
        out = jnp.einsum("bshv,hvd->bsd", out, params["wo"])
        return out, ({"ckv": ckv, "krope": k_rope} if return_kv else None)

    # ---- absorbed decode: attend in the compressed latent space ----------
    assert S == 1 and cache_view is not None
    slot = cache_view["slot"]
    ckv_cache = jax.vmap(lambda c, s, u: jax.lax.dynamic_update_slice(c, u, (s, 0)))(
        cache["ckv"], slot, ckv
    )
    krope_cache = jax.vmap(lambda c, s, u: jax.lax.dynamic_update_slice(c, u, (s, 0)))(
        cache["krope"], slot, k_rope
    )
    if plan is not None:
        ckv_cache = plan.constrain(ckv_cache, "batch", "cache_seq", None)
        krope_cache = plan.constrain(krope_cache, "batch", "cache_seq", None)

    w_uk = params["wkv_b"][..., :nope]  # (r, H, nope)
    w_uv = params["wkv_b"][..., nope:]  # (r, H, v_dim)
    q_lat_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
    scores = (
        jnp.einsum("bqhr,bsr->bhqs", q_lat_abs, ckv_cache, preferred_element_type=jnp.float32)
        + jnp.einsum("bqhp,bsp->bhqs", q_rope, krope_cache, preferred_element_type=jnp.float32)
    ) * scale
    mask = _mask(positions, cache_view["slot_pos"], cfg.sliding_window)[:, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(ckv_cache.dtype)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", probs, ckv_cache)
    o = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv)
    out = jnp.einsum("bshv,hvd->bsd", o, params["wo"])
    return out, {"ckv": ckv_cache, "krope": krope_cache}


def attention_decl(cfg: ModelConfig) -> Dict[str, Any]:
    return mla_decl(cfg) if cfg.use_mla else gqa_decl(cfg)


def attention_apply(cfg, plan, params, x, positions, cache=None, cache_view=None,
                    return_kv=False, use_kernel=False):
    if cfg.use_mla:
        return mla_apply(cfg, plan, params, x, positions, cache, cache_view,
                         return_kv, use_kernel=use_kernel)
    return gqa_apply(cfg, plan, params, x, positions, cache, cache_view,
                     return_kv=return_kv, use_kernel=use_kernel)
