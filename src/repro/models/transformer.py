"""Transformer block stacks: dense, MoE, SSM, hybrid (jamba), enc-dec.

Layers are organized as (periods x slots): a *slot* is one block kind
(mixer in {attn, ssm} x ffn in {dense, moe, none}); the stack repeats the
slot list ``periods`` times via ``lax.scan`` over stacked parameters. This
keeps the HLO size O(slots) regardless of depth (critical for compiling
72-layer Jamba on 512 fake devices) and is the PP-replacement documented in
DESIGN.md. Heterogeneous patterns (jamba's M M M A M M M M mixer period,
MoE-every-2nd-layer) become slot lists.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core.moe import moe_apply, moe_decl
from repro.models.attention import attention_apply, attention_decl, gqa_apply, gqa_decl
from repro.models.layers import mlp_apply, mlp_decl, norm_apply, norm_decl
from repro.models.ssm import ssm_apply, ssm_cache_decl, ssm_decl
from repro.sharding.rules import FoldingPlan, ParamDecl


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    mixer: str  # 'attn' | 'ssm'
    ffn: str  # 'dense' | 'moe' | 'none'
    cross_attn: bool = False
    causal: bool = True


def build_slots(cfg: ModelConfig) -> List[BlockSpec]:
    """Slot list for one period of the decoder stack."""
    moe = cfg.moe
    moe_freq = moe.moe_layer_freq if moe is not None else 1
    if cfg.family == "ssm":
        return [BlockSpec("ssm", "dense" if cfg.d_ff else "none")]
    if cfg.family == "hybrid":
        pat = cfg.hybrid_pattern or "M"
        period = len(pat)
        if moe is not None and period % moe_freq != 0:
            period = period * moe_freq
        slots = []
        for i in range(period):
            mixer = "ssm" if (cfg.hybrid_pattern or "M")[i % len(cfg.hybrid_pattern or "M")] == "M" else "attn"
            ffn = "dense"
            if moe is not None and (i % moe_freq) == (moe_freq - 1):
                ffn = "moe"
            slots.append(BlockSpec(mixer, ffn))
        return slots
    # dense / moe / vlm / encdec-decoder
    slots = []
    for i in range(moe_freq):
        ffn = "moe" if (moe is not None and i == moe_freq - 1) else "dense"
        slots.append(
            BlockSpec("attn", ffn, cross_attn=(cfg.family == "encdec"))
        )
    return slots


def periods_for(cfg: ModelConfig, slots: List[BlockSpec]) -> int:
    assert cfg.num_layers % len(slots) == 0, (cfg.num_layers, len(slots))
    return cfg.num_layers // len(slots)


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------


def block_decl(cfg: ModelConfig, spec: BlockSpec) -> Dict[str, Any]:
    decls: Dict[str, Any] = {"norm1": norm_decl(cfg.d_model, cfg.norm_type)}
    decls["mixer"] = ssm_decl(cfg) if spec.mixer == "ssm" else attention_decl(cfg)
    if spec.cross_attn:
        decls["norm_cross"] = norm_decl(cfg.d_model, cfg.norm_type)
        decls["cross"] = gqa_decl(cfg)
    if spec.ffn != "none":
        decls["norm2"] = norm_decl(cfg.d_model, cfg.norm_type)
        if spec.ffn == "moe":
            assert cfg.moe is not None
            decls["ffn"] = moe_decl(cfg, cfg.moe)
        else:
            decls["ffn"] = mlp_decl(cfg.d_model, cfg.d_ff)
    return decls


def block_apply(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    spec: BlockSpec,
    params,
    x: jax.Array,
    positions: jax.Array,
    rng: Optional[jax.Array] = None,
    train: bool = False,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_view: Optional[Dict[str, jax.Array]] = None,
    cross_ctx: Optional[Tuple[jax.Array, jax.Array]] = None,  # (enc_out, enc_pos)
    use_kernel: bool = False,
    return_cache: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]], Dict[str, jax.Array]]:
    aux: Dict[str, jax.Array] = {}
    with jax.named_scope("norm"):
        h = norm_apply(params["norm1"], x, cfg.norm_type, cfg.norm_eps)
    new_cache: Dict[str, jax.Array] = {}
    if spec.mixer == "ssm":
        mix, c = ssm_apply(
            cfg, plan, params["mixer"], h,
            cache.get("ssm") if cache else None, return_state=return_cache,
        )
        if c is not None:
            new_cache["ssm"] = c
    else:
        with jax.named_scope("attention"):
            if spec.mixer == "attn" and not spec.causal:
                mix, c = gqa_apply(
                    cfg, plan, params["mixer"], h, positions, causal=False,
                    use_kernel=use_kernel,
                )
            else:
                mix, c = attention_apply(
                    cfg, plan, params["mixer"], h, positions,
                    cache.get("attn") if cache else None, cache_view,
                    return_kv=return_cache, use_kernel=use_kernel,
                )
        if c is not None:
            new_cache["attn"] = c
    x = x + mix

    if spec.cross_attn:
        assert cross_ctx is not None or (cache is not None and "cross" in cache)
        with jax.named_scope("norm"):
            h = norm_apply(params["norm_cross"], x, cfg.norm_type, cfg.norm_eps)
        if cache is not None and "cross" in cache:
            ck, cv, cp = cache["cross"]["k"], cache["cross"]["v"], cache_view["enc_pos"]
            new_cache["cross"] = cache["cross"]
        else:
            enc_out, cp = cross_ctx
            ck = jnp.einsum("bsd,dhk->bshk", enc_out, params["cross"]["wk"])
            cv = jnp.einsum("bsd,dhk->bshk", enc_out, params["cross"]["wv"])
        cx, _ = gqa_apply(
            cfg, plan, params["cross"], h, positions, cross_kv=(ck, cv, cp)
        )
        x = x + cx

    if spec.ffn != "none":
        with jax.named_scope("norm"):
            h = norm_apply(params["norm2"], x, cfg.norm_type, cfg.norm_eps)
        if spec.ffn == "moe":
            y, aux = moe_apply(
                cfg, cfg.moe, plan, params["ffn"], h, rng, train, use_kernel
            )
        else:
            y = mlp_apply(params["ffn"], h)
            if plan is not None:
                y = plan.constrain(y, "fold_batch", None, None)
        x = x + y
    return x, (new_cache or None), aux


# ---------------------------------------------------------------------------
# Stack: scan over periods
# ---------------------------------------------------------------------------


def _stack_decl_one(cfg: ModelConfig, spec: BlockSpec, periods: int):
    """Block decls with a leading stacked 'layers' dim of size ``periods``."""
    decls = block_decl(cfg, spec)

    def stack(d: ParamDecl) -> ParamDecl:
        return ParamDecl((periods,) + d.shape, ("layers",) + d.axes, d.init, d.dtype)

    return jax.tree.map(stack, decls, is_leaf=lambda d: isinstance(d, ParamDecl))


def stack_decl(cfg: ModelConfig, slots: List[BlockSpec], periods: int) -> Dict[str, Any]:
    return {f"slot{i}": _stack_decl_one(cfg, s, periods) for i, s in enumerate(slots)}


AUX_KEYS = ("load_balance_loss", "z_loss")


def stack_apply(
    cfg: ModelConfig,
    plan: Optional[FoldingPlan],
    slots: List[BlockSpec],
    params: Dict[str, Any],
    x: jax.Array,
    positions: jax.Array,
    rng: Optional[jax.Array] = None,
    train: bool = False,
    cache: Optional[Dict[str, Any]] = None,
    cache_view: Optional[Dict[str, jax.Array]] = None,
    cross_ctx=None,
    use_kernel: bool = False,
    return_cache: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, Any]], Dict[str, jax.Array]]:
    """params[slot_i] leaves have leading (periods,) dim; scanned.
    cache mirrors the structure with the same leading dim."""
    periods = jax.tree.leaves(params["slot0"])[0].shape[0]
    keys = (
        jax.random.split(rng, periods * len(slots)).reshape(periods, len(slots), -1)
        if rng is not None
        else jnp.zeros((periods, len(slots), 2), jnp.uint32)
    )

    def body(carry, xs):
        h, aux_acc = carry
        layer_params, layer_cache, layer_keys = xs
        new_caches = {}
        for i, spec in enumerate(slots):
            sk = f"slot{i}"
            ck = layer_cache.get(sk) if layer_cache else None
            k_i = layer_keys[i] if rng is not None else None
            h, nc, aux = block_apply(
                cfg, plan, spec, layer_params[sk], h, positions, k_i, train,
                ck, cache_view, cross_ctx, use_kernel, return_cache,
            )
            if nc is not None:
                new_caches[sk] = nc
            for k in AUX_KEYS:
                if k in aux:
                    aux_acc = {**aux_acc, k: aux_acc[k] + aux[k]}
        return (h, aux_acc), (new_caches or None)

    if cfg.remat != "none" and train:
        body = jax.checkpoint(body, prevent_cse=False)

    aux0 = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}
    with jax.named_scope("layer_scan"):
        (x, aux), new_cache = jax.lax.scan(body, (x, aux0), (params, cache, keys))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Cache declarations
# ---------------------------------------------------------------------------


def block_cache_decl(
    cfg: ModelConfig, spec: BlockSpec, batch: int, cache_len: int, enc_len: int = 0
) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    dt = jnp.dtype(cfg.dtype)
    if spec.mixer == "ssm":
        out["ssm"] = ssm_cache_decl(cfg, batch)
    elif cfg.use_mla:
        m = cfg.mla
        out["attn"] = {
            "ckv": ParamDecl(
                (batch, cache_len, m.kv_lora_rank), ("batch", "cache_seq", None), "zeros", dt
            ),
            "krope": ParamDecl(
                (batch, cache_len, m.qk_rope_head_dim), ("batch", "cache_seq", None), "zeros", dt
            ),
        }
    else:
        kv, hd = cfg.num_kv_heads, cfg.head_dim_
        out["attn"] = {
            "k": ParamDecl(
                (batch, cache_len, kv, hd), ("batch", "cache_seq", None, None), "zeros", dt
            ),
            "v": ParamDecl(
                (batch, cache_len, kv, hd), ("batch", "cache_seq", None, None), "zeros", dt
            ),
        }
    if spec.cross_attn:
        kv, hd = cfg.num_kv_heads, cfg.head_dim_
        out["cross"] = {
            "k": ParamDecl(
                (batch, enc_len, kv, hd), ("batch", None, "kv_heads", None), "zeros", dt
            ),
            "v": ParamDecl(
                (batch, enc_len, kv, hd), ("batch", None, "kv_heads", None), "zeros", dt
            ),
        }
    return out


def stack_cache_decl(
    cfg: ModelConfig,
    slots: List[BlockSpec],
    periods: int,
    batch: int,
    cache_len: int,
    enc_len: int = 0,
) -> Dict[str, Any]:
    def stack(d: ParamDecl) -> ParamDecl:
        return ParamDecl((periods,) + d.shape, ("layers",) + d.axes, d.init, d.dtype)

    out = {}
    for i, s in enumerate(slots):
        c = block_cache_decl(cfg, s, batch, cache_len, enc_len)
        out[f"slot{i}"] = jax.tree.map(
            stack, c, is_leaf=lambda d: isinstance(d, ParamDecl)
        )
    return out
