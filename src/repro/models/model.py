"""Top-level language model: embeddings + stack(s) + head, with three entry
points used by the launcher and dry-run:

* ``forward``     — training/prefill forward over full sequences.
* ``loss_fn``     — CE over the (padded, vocab-sharded) logits + MoE aux.
* ``decode_step`` — one new token against the ring KV/SSM cache (serve_step).
* ``paged_forward`` / ``decode_step_paged`` — chunked prefill and decode
  against the block-table page pool (serving cache_mode="paged").

Multimodal stubs (DESIGN.md carve-out): ``vlm`` consumes a precomputed patch
-embedding prefix; ``encdec`` (audio) consumes precomputed frame embeddings
on the encoder side. Both are supplied by ``input_specs`` as arrays of the
right shape — the backbone is fully implemented, the frontend is not.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import (
    cross_entropy,
    embed_apply,
    embed_decl,
    norm_apply,
    norm_decl,
    unembed_apply,
)
from repro.models.transformer import (
    BlockSpec,
    build_slots,
    periods_for,
    stack_apply,
    stack_cache_decl,
    stack_decl,
)
from repro.sharding.rules import FoldingPlan, ParamDecl


def model_decl(cfg: ModelConfig) -> Dict[str, Any]:
    slots = build_slots(cfg)
    periods = periods_for(cfg, slots)
    decls: Dict[str, Any] = {
        "embed": embed_decl(cfg.padded_vocab, cfg.d_model, cfg.tie_embeddings),
        "stack": stack_decl(cfg, slots, periods),
        "final_norm": norm_decl(cfg.d_model, cfg.norm_type),
    }
    if cfg.family == "encdec":
        enc_slots = [BlockSpec("attn", "dense", causal=False)]
        assert cfg.num_encoder_layers > 0
        decls["encoder"] = stack_decl(cfg, enc_slots, cfg.num_encoder_layers)
        decls["encoder_norm"] = norm_decl(cfg.d_model, cfg.norm_type)
    if cfg.quant_weights == "int8":
        # serving-side int8 expert weights: expert decls become int8 and
        # gain bf16 per-output-channel scale decls that keep the leading
        # ("expert", ...) axis, so EP sharding splits scales with experts
        from repro.core.quant import quantize_decls

        decls = quantize_decls(decls)
    return decls


def _encode(cfg, plan, params, frames: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Run the (non-causal) encoder over stub frame embeddings (B,Se,D)."""
    B, Se, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    enc_slots = [BlockSpec("attn", "dense", causal=False)]
    x = frames.astype(jnp.dtype(cfg.dtype))
    if plan is not None:
        x = plan.constrain(x, "batch", None, None)
    x, _, _ = stack_apply(cfg, plan, enc_slots, params["encoder"], x, pos)
    x = norm_apply(params["encoder_norm"], x, cfg.norm_type, cfg.norm_eps)
    return x, pos


def forward(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    params,
    batch: Dict[str, jax.Array],
    rng: Optional[jax.Array] = None,
    train: bool = False,
    use_kernel: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (logits over text positions, aux). batch keys:
    tokens (B,St); vlm: + embeds (B,P,D); encdec: + frames (B,Se,D)."""
    tokens = batch["tokens"]
    B, St = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = embed_apply(params["embed"], tokens, dtype)
    prefix = 0
    cross_ctx = None
    if cfg.family == "vlm":
        emb = batch["embeds"].astype(x.dtype)
        prefix = emb.shape[1]
        x = jnp.concatenate([emb, x], axis=1)
    elif cfg.family == "encdec":
        cross_ctx = _encode(cfg, plan, params, batch["frames"])
    S = x.shape[1]
    if plan is not None:
        x = plan.constrain(x, "fold_batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    slots = build_slots(cfg)
    x, _, aux = stack_apply(
        cfg, plan, slots, params["stack"], x, positions, rng, train,
        cross_ctx=cross_ctx, use_kernel=use_kernel,
    )
    with jax.named_scope("head_loss"):
        x = norm_apply(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        if prefix:
            x = x[:, prefix:]
        logits = unembed_apply(params["embed"] if cfg.tie_embeddings else params["embed"], x)
    if plan is not None:
        logits = plan.constrain(logits, "fold_batch", None, "vocab")
    return logits, aux


def loss_fn(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    params,
    batch: Dict[str, jax.Array],
    rng: Optional[jax.Array] = None,
    use_kernel: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    # one scope around the whole loss, so that the layer scopes inside come
    # out as plain op-name segments under jvp( and transpose( (a scope at
    # the top of a differentiated function is folded into the transform's
    # own segment, and its transposed ops lose it)
    with jax.named_scope("model"):
        logits, aux = forward(cfg, plan, params, batch, rng, train=True, use_kernel=use_kernel)
        with jax.named_scope("head_loss"):
            ce = cross_entropy(logits, batch["labels"], cfg.vocab_size)
        loss = ce + sum(aux.values())
    metrics = {"loss": loss, "ce": ce, **aux}
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------


def cache_decl(
    cfg: ModelConfig, batch: int, cache_len: int, enc_len: int = 0
) -> Dict[str, Any]:
    """Cache structure for decode. cache_len = min(seq_len, sliding_window)."""
    if cfg.sliding_window is not None:
        cache_len = min(cache_len, cfg.sliding_window)
    slots = build_slots(cfg)
    periods = periods_for(cfg, slots)
    decls: Dict[str, Any] = {
        "pos": ParamDecl((batch,), ("batch",), "zeros", jnp.int32),
        "slot_pos": ParamDecl((batch, cache_len), ("batch", "cache_seq"), "zeros", jnp.int32),
        "stack": stack_cache_decl(cfg, slots, periods, batch, cache_len, enc_len),
    }
    return decls


def decode_step(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    params,
    cache: Dict[str, Any],
    tokens: jax.Array,  # (B,) next input token ids
    use_kernel: bool = False,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step: writes token at cache position, returns fp32 logits
    (B, padded_vocab) for the next token and the updated cache."""
    B = tokens.shape[0]
    pos = cache["pos"]  # (B,)
    W = cache["slot_pos"].shape[1]
    slot = (pos % W).astype(jnp.int32)
    slot_pos = cache["slot_pos"].at[jnp.arange(B), slot].set(pos)
    # unfilled slots must stay invalid: init slot_pos to -1 via pos==0 reset
    slot_pos = jnp.where(
        (cache["pos"][:, None] == 0)
        & (jnp.arange(W)[None, :] != slot[:, None]),
        -1,
        slot_pos,
    )
    cache_view = {"slot": slot, "slot_pos": slot_pos}
    if cfg.family == "encdec":
        enc_len = jax.tree.leaves(cache["stack"]["slot0"]["cross"])[0].shape[2]
        cache_view["enc_pos"] = jnp.broadcast_to(
            jnp.arange(enc_len, dtype=jnp.int32), (B, enc_len)
        )

    x = embed_apply(params["embed"], tokens[:, None], jnp.dtype(cfg.dtype))  # (B,1,D)
    if plan is not None:
        x = plan.constrain(x, "batch", None, None)
    positions = pos[:, None]

    slots = build_slots(cfg)
    x, new_stack, _ = stack_apply(
        cfg, plan, slots, params["stack"], x, positions,
        cache=cache["stack"], cache_view=cache_view, use_kernel=use_kernel,
    )
    x = norm_apply(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    logits = unembed_apply(params["embed"], x)[:, 0]
    if plan is not None:
        logits = plan.constrain(logits, "batch", "vocab")
    new_cache = {"pos": pos + 1, "slot_pos": slot_pos, "stack": new_stack}
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged-KV decode / chunked prefill (serving/kv_cache.py drives these)
# ---------------------------------------------------------------------------


def paged_stack_decl(cfg: ModelConfig, num_pages: int, page_size: int) -> Dict[str, Any]:
    """KV page-pool declarations: per layer-slot ``(P, num_pages, KV,
    page_size, hd)`` k/v pools shared by every sequence (a KV head's
    ``(page_size, hd)`` tile is the minor pair — the paged-attention
    kernel's block). By convention the LAST
    page (index ``num_pages - 1``) is the trash page — padded positions
    scatter there and it never appears in a block table; callers allocating
    N usable pages must decl N + 1. Under EP x DP serving the pool is a
    concatenation of per-DP-shard strides, each ending in its own trash
    page (``serving.kv_cache.PagePool`` owns that layout; rows then pass a
    per-row ``trash_page`` to :func:`paged_forward` so idle writes stay in
    their shard's stride).

    Paged mode covers GQA attention stacks only (dense / moe / vlm-as-text
    families); MLA, SSM and cross-attention configs keep the ring cache.

    ``cfg.quant_kv == "int8"`` switches the k/v payload to int8 and adds
    per-token, per-kv-head f32 scale sidecar leaves (``k_scale``/
    ``v_scale``, head dim collapsed to 1) to the same pool subtree. The
    sidecars keep the page axis at 1 and the page_size axis at 3, so every
    pool-tree operation (``copy_pages`` COW, ``permute_pool`` defrag,
    ``pool_sharding`` DP split, the first-leaf shape introspection below)
    moves scales with their pages structurally."""
    slots = build_slots(cfg)
    periods = periods_for(cfg, slots)
    assert not cfg.use_mla and all(
        s.mixer == "attn" and not s.cross_attn for s in slots
    ), "paged KV cache supports GQA attention stacks only"
    kv, hd = cfg.num_kv_heads, cfg.head_dim_
    quant = getattr(cfg, "quant_kv", "none") == "int8"
    dt = jnp.dtype(jnp.int8) if quant else jnp.dtype(cfg.dtype)

    def pool():
        kv_decl = lambda: ParamDecl(
            (periods, num_pages, kv, page_size, hd),
            ("layers", None, None, None, None), "zeros", dt,
        )
        attn = {"k": kv_decl(), "v": kv_decl()}
        if quant:
            scale_decl = lambda: ParamDecl(
                (periods, num_pages, kv, page_size, 1),
                ("layers", None, None, None, None), "zeros", jnp.float32,
            )
            attn["k_scale"] = scale_decl()
            attn["v_scale"] = scale_decl()
        return {"attn": attn}

    return {"stack": {f"slot{i}": pool() for i in range(len(slots))}}


def paged_forward(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    params,
    pool: Dict[str, Any],
    tokens: jax.Array,  # (B, S) chunk of token ids (right-padded per bucket)
    pos_start: jax.Array,  # (B,) absolute position of tokens[:, 0]
    page_table: jax.Array,  # (B, max_pages) int32 page ids, -1 = unassigned
    valid_len: jax.Array,  # (B,) real tokens in this chunk (0 = idle slot)
    use_kernel: bool = False,
    trash_page: Optional[jax.Array] = None,  # (B,) per-row trash page id
    return_all_logits: bool = False,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One forward over the page-table cache view: S > 1 is a prefill chunk
    (attends to previously-written pages + the chunk itself, causally),
    S == 1 is single-token decode. Logical KV slot ``j`` of sequence ``b``
    lives at ``pool[page_table[b, j // ps], j % ps]`` — the identity
    position mapping (pages never wrap, unlike the ring cache).

    Writes for padded / idle positions are routed to the trash page, so the
    compiled step is shared across every request in a length bucket.
    ``trash_page`` overrides the default last-page convention per row: the
    EP x DP engine passes each batch row its DP shard's own trash page so
    idle writes never cross the shard's stride of the page axis.
    Returns (fp32 logits (B, padded_vocab) at each row's last valid
    position, updated pool).

    ``return_all_logits=True`` unembeds every chunk position instead —
    logits (B, S, padded_vocab) — which is what the speculative-decoding
    verify step needs: position j's logits give the target model's next
    token after draft token j, so one chunk scores k drafts at once
    (positions >= valid_len are pad garbage; callers mask by length)."""
    B, S = tokens.shape
    leaf = jax.tree.leaves(pool["stack"])[0]  # (P, num_pages, KV, ps, hd)
    num_pages, ps = leaf.shape[1], leaf.shape[3]
    maxP = page_table.shape[1]
    trash = (
        jnp.full((B, 1), num_pages - 1, jnp.int32)
        if trash_page is None else trash_page.astype(jnp.int32)[:, None]
    )

    positions = pos_start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    pvalid = jnp.arange(S, dtype=jnp.int32)[None, :] < valid_len[:, None]
    wp = jnp.take_along_axis(page_table, positions // ps, axis=1)  # (B, S)
    wp = jnp.where(pvalid & (wp >= 0), wp, trash)
    wo = positions % ps
    seq_lens = pos_start + valid_len
    kpos = jnp.arange(maxP * ps, dtype=jnp.int32)
    k_pos = jnp.where(
        (kpos[None, :] < seq_lens[:, None]) & (page_table[:, kpos // ps] >= 0),
        kpos[None, :], -1,
    )
    cache_view = {
        "page_table": page_table, "k_pos": k_pos,
        "write_page": wp, "write_offset": wo, "seq_lens": seq_lens,
    }

    x = embed_apply(params["embed"], tokens, jnp.dtype(cfg.dtype))
    if plan is not None:
        x = plan.constrain(x, "batch", None, None)
    slots = build_slots(cfg)
    x, new_stack, _ = stack_apply(
        cfg, plan, slots, params["stack"], x, positions,
        cache=pool["stack"], cache_view=cache_view, use_kernel=use_kernel,
    )
    x = norm_apply(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    if return_all_logits:
        logits = unembed_apply(params["embed"], x)  # (B, S, V)
        if plan is not None:
            logits = plan.constrain(logits, "batch", None, "vocab")
        return logits, {"stack": new_stack}
    last = jnp.maximum(valid_len - 1, 0)
    xl = x[jnp.arange(B), last][:, None]  # (B, 1, D)
    logits = unembed_apply(params["embed"], xl)[:, 0]
    if plan is not None:
        logits = plan.constrain(logits, "batch", "vocab")
    return logits, {"stack": new_stack}


def decode_step_paged(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    params,
    pool: Dict[str, Any],
    tokens: jax.Array,  # (B,) next input token ids
    pos: jax.Array,  # (B,) absolute position to write
    page_table: jax.Array,  # (B, max_pages)
    active: jax.Array,  # (B,) 1 for live slots, 0 for idle
    use_kernel: bool = False,
    trash_page: Optional[jax.Array] = None,  # (B,) per-row trash page id
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Single-token paged decode: ``paged_forward`` with a length-1 chunk.
    Idle slots write to the trash page and emit garbage logits (ignored by
    the engine)."""
    return paged_forward(
        cfg, plan, params, pool, tokens[:, None], pos, page_table,
        active.astype(jnp.int32), use_kernel=use_kernel,
        trash_page=trash_page,
    )


def prefill_forward(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    params,
    batch: Dict[str, jax.Array],
    cache_len: Optional[int] = None,
    use_kernel: bool = False,
    valid_len: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Fused prefill: one full-sequence forward that also emits a decode-
    ready cache (prefill_32k lowers this). For sliding-window configs the
    last W keys are ring-packed into their slots.

    ``valid_len`` (B,) enables length-bucketed prefill: tokens are
    right-padded to a shared bucket shape, logits are taken at each row's
    last *valid* position, and the pad slots are marked invalid in
    ``slot_pos`` (decode then overwrites them in order). Callers must keep
    the padded length <= the ring size so padding never wraps over valid
    entries."""
    tokens = batch["tokens"]
    B, St = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    x = embed_apply(params["embed"], tokens, dtype)
    prefix = 0
    cross_ctx = None
    if cfg.family == "vlm":
        emb = batch["embeds"].astype(x.dtype)
        prefix = emb.shape[1]
        x = jnp.concatenate([emb, x], axis=1)
    elif cfg.family == "encdec":
        cross_ctx = _encode(cfg, plan, params, batch["frames"])
    S = x.shape[1]
    if plan is not None:
        x = plan.constrain(x, "fold_batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    slots = build_slots(cfg)
    x, seq_cache, _ = stack_apply(
        cfg, plan, slots, params["stack"], x, positions,
        cross_ctx=cross_ctx, use_kernel=use_kernel, return_cache=True,
    )
    x = norm_apply(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
    if valid_len is None:
        total = jnp.full((B,), S, jnp.int32)
        xl = x[:, -1:]
    else:
        total = prefix + valid_len.astype(jnp.int32)
        xl = x[jnp.arange(B), total - 1][:, None]
    logits = unembed_apply(params["embed"], xl)[:, 0]

    # ---- pack the per-layer seq caches into the ring-buffer layout -------
    W = cache_len or S
    if cfg.sliding_window is not None:
        W = min(W, cfg.sliding_window)
    Wc = min(W, S)
    ring_slots = (S - Wc + jnp.arange(Wc)) % W  # where the last Wc keys go

    def pack(full):  # full: (P, B, S, ...) stacked seq cache
        buf = jnp.zeros(full.shape[:2] + (W,) + full.shape[3:], full.dtype)
        return buf.at[:, :, ring_slots].set(full[:, :, S - Wc :])

    def pack_tree(c):
        out = {}
        for k, v in c.items():
            if k == "ssm":
                out[k] = v  # state caches carry no seq dim
            elif k == "cross":
                out[k] = v
            else:
                out[k] = jax.tree.map(pack, v)
        return out

    stack_cache = {sk: pack_tree(c) for sk, c in (seq_cache or {}).items()}
    if cfg.family == "encdec":
        enc_out, _ = cross_ctx
        for i in range(len(slots)):
            sk = f"slot{i}"
            ck = jnp.einsum("bsd,pdhk->pbshk", enc_out, params["stack"][sk]["cross"]["wk"])
            cv = jnp.einsum("bsd,pdhk->pbshk", enc_out, params["stack"][sk]["cross"]["wv"])
            stack_cache[sk]["cross"] = {"k": ck, "v": cv}
    slot_pos = jnp.full((B, W), -1, jnp.int32)
    slot_pos = slot_pos.at[:, ring_slots].set(
        jnp.broadcast_to(jnp.arange(S - Wc, S, dtype=jnp.int32), (B, Wc))
    )
    if valid_len is not None:
        # pad slots stay invalid; decode overwrites them position-in-order
        slot_pos = jnp.where(slot_pos >= total[:, None], -1, slot_pos)
    cache = {
        "pos": total,
        "slot_pos": slot_pos,
        "stack": stack_cache,
    }
    return logits, cache


def prefill_reference(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    params,
    batch: Dict[str, jax.Array],
    cache_len: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Fill a decode cache by running decode_step over the prompt via scan.
    Oracle for prefill_forward in tests."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    cache_len = cache_len or S
    from repro.sharding.rules import init_from_decls

    decls = cache_decl(cfg, B, cache_len, enc_len=batch.get("frames", jnp.zeros((B, 0, cfg.d_model))).shape[1])
    cache = jax.tree.map(
        lambda d: jnp.zeros(d.shape, d.dtype),
        decls,
        is_leaf=lambda d: isinstance(d, ParamDecl),
    )
    # slot_pos starts invalid
    cache["slot_pos"] = jnp.full_like(cache["slot_pos"], -1)
    if cfg.family == "encdec":
        enc_out, _ = _encode(cfg, plan, params, batch["frames"])
        new_cross = {}
        slots = build_slots(cfg)
        periods = periods_for(cfg, slots)
        for i in range(len(slots)):
            sk = f"slot{i}"
            wk = params["stack"][sk]["cross"]["wk"]
            wv = params["stack"][sk]["cross"]["wv"]
            ck = jnp.einsum("bsd,pdhk->pbshk", enc_out, wk)
            cv = jnp.einsum("bsd,pdhk->pbshk", enc_out, wv)
            cache["stack"][sk]["cross"] = {"k": ck, "v": cv}

    def step(cache, tok):
        logits, cache = decode_step(cfg, plan, params, cache, tok)
        return cache, logits

    cache, logits = jax.lax.scan(step, cache, tokens.T)
    return logits[-1], cache
