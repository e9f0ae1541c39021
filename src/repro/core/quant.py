"""Symmetric int8 quantization for expert FFN weights and KV cache pages.

Two quantization surfaces, both serving/inference-only (training and the
PR 2 backward kernels stay bf16):

Expert weights (``quantize_experts``): per-expert, per-*output-channel*
symmetric scales — gate/up scale over F, down over D. Because the scale is
constant along the contraction dim, dequantization commutes with the
matmul: the Pallas kernels (kernels/expert_gemm.py) load int8 weight
tiles, accumulate in fp32, and apply the scale once in the epilogue — an
*exact* rewrite of dequantize-then-matmul, so kernel-vs-oracle parity is
tight and the only error is the rounding step itself. Scales are bf16 and
carry the same leading ``("expert", ...)`` logical axis as their weights,
so `FoldingPlan`/EP sharding splits them alongside their experts
(``quantize_decls``).

KV pages (``quantize_kv``): per-written-token, per-kv-head symmetric
scales stored in a sidecar pool leaf shaped ``(periods, num_pages, KV,
page_size, 1)``. Page-granular scales cannot survive incremental
decode writes (a later token cannot retroactively rescale the page), so
the sidecar is indexed exactly like the page payload and rides every
pool-tree operation (COW ``copy_pages``, defrag ``permute_pool``, DP
``pool_sharding``) structurally — the no-desync property tested in
tests/test_quant.py. Sidecar scales are f32: they are ~3% of page bytes
and keep the dequant error budget for greedy-token parity.

Error-budget contract (asserted in tests/test_quant.py):
* kernel vs quantized oracle: allclose at ``KERNEL_PARITY_TOL`` (the
  kernels are an exact rewrite; only accumulation order differs);
* quantized vs bf16 model: final-layer logits within
  ``INT8_LOGIT_BUDGET`` max-abs on the e8t2 smoke config, and greedy
  tokens *exactly* equal over a short decode.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

EXPERT_KEYS = ("w_gate", "w_up", "w_down")
QUANT_MODES = ("none", "int8")

# --- error-budget contract (see tests/test_quant.py) -----------------------
# int8 kernel vs the *quantized* oracle: same math, different accumulation
# order -> tight.
KERNEL_PARITY_TOL = 2e-2
# quantized-weight logits vs the bf16 model on the e8t2 smoke config
# (max-abs over the final logits; int8 rounding error through 2 MoE layers).
INT8_LOGIT_BUDGET = 0.25
# quantized-KV decode logits vs bf16 pages, single step.
INT8_KV_LOGIT_BUDGET = 0.25

_EPS = 1e-8
KV_SCALE_DTYPE = jnp.float32


def quantize_weight(w: jax.Array):
    """``(..., K, C) -> (int8 (..., K, C), bf16 (..., C))`` symmetric
    per-output-channel abs-max scales (axis -2 is the contraction dim)."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2)
    scale = jnp.maximum(amax, _EPS) / 127.0
    q = jnp.clip(jnp.round(wf / scale[..., None, :]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def dequantize_weight(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None, :]).astype(dtype)


def is_quantized(experts: Dict[str, jax.Array]) -> bool:
    return "w_gate_scale" in experts


def quantize_experts(experts: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Quantize an expert-FFN param dict ``{w_gate, w_up, w_down}`` (any
    leading dims, e.g. scanned layers) into int8 values + ``*_scale``
    bf16 sidecar entries. Idempotent on already-quantized dicts."""
    if is_quantized(experts):
        return experts
    out = dict(experts)
    for k in EXPERT_KEYS:
        q, s = quantize_weight(experts[k])
        out[k] = q
        out[k + "_scale"] = s
    return out


def dequantize_experts(experts: Dict[str, jax.Array], dtype) -> Dict[str, jax.Array]:
    """Inverse of :func:`quantize_experts` for XLA fallback paths (the
    einsum/ragged_dot dispatchers that don't carry fused-dequant kernels)."""
    if not is_quantized(experts):
        return experts
    return {
        k: dequantize_weight(experts[k], experts[k + "_scale"], dtype)
        for k in EXPERT_KEYS
    }


def _is_expert_dict(node) -> bool:
    return isinstance(node, dict) and all(k in node for k in EXPERT_KEYS)


def quantize_params(params):
    """Walk a model param pytree and quantize every expert-FFN dict in
    place (structurally — returns a new tree). Non-expert leaves pass
    through untouched; attention/embedding/router stay bf16."""
    if _is_expert_dict(params):
        return quantize_experts(params)
    if isinstance(params, dict):
        return {k: quantize_params(v) for k, v in params.items()}
    if isinstance(params, (list, tuple)):
        return type(params)(quantize_params(v) for v in params)
    return params


def quantize_decls(decls):
    """Mirror of :func:`quantize_params` over a ``ParamDecl`` tree: expert
    weight decls become int8 and gain bf16 ``*_scale`` decls whose axes
    drop the contraction dim — the leading ``("expert", ...)`` logical
    axis is preserved so scales shard alongside their experts under the
    FoldingPlan/EP rules."""
    import dataclasses

    from repro.sharding.rules import ParamDecl

    def _q(node):
        if _is_expert_dict(node) and all(
            isinstance(node[k], ParamDecl) for k in EXPERT_KEYS
        ):
            out = dict(node)
            for k in EXPERT_KEYS:
                d = node[k]
                out[k] = dataclasses.replace(d, dtype=jnp.int8, init="zeros")
                out[k + "_scale"] = ParamDecl(
                    d.shape[:-2] + d.shape[-1:],
                    d.axes[:-2] + d.axes[-1:],
                    "ones",
                    jnp.bfloat16,
                )
            return out
        if isinstance(node, dict):
            return {k: _q(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(_q(v) for v in node)
        return node

    return _q(decls)


# --- KV page quantization ---------------------------------------------------


def quantize_kv(x: jax.Array):
    """``(..., d) -> (int8 (..., d), f32 (..., 1))`` per-vector (token x
    kv-head) symmetric scales — the granularity that survives incremental
    page writes."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, _EPS) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(KV_SCALE_DTYPE)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


# --- greedy-parity probe model ---------------------------------------------


def sharpen_for_parity(cfg, params, steps: int = 80, seed: int = 0,
                       seq_len: int = 64, batch: int = 8, period: int = 32,
                       lr: float = 0.5):
    """Fit a greedy-parity probe: a few SGD steps on a fixed periodic token
    stream (a deterministic next-token task the smoke model memorizes).

    Greedy-token parity checked against a *random-init* model is vacuous —
    its logits are near-uniform, so argmax flips under any perturbation,
    int8 rounding included. After this, top-1 margins are O(1) while the
    int8 error budget is O(0.01), so "exact greedy parity" becomes a
    seed-robust, meaningful assertion (tests/test_quant.py and the
    BENCH_serving quant section both use it).

    Returns ``(params, pattern)``: the sharpened params and the (period,)
    int32 token pattern — build prompts from slices of it so decode stays
    in-distribution where the margins are."""
    import numpy as np

    from repro.models.model import loss_fn

    rng = np.random.RandomState(seed)
    pattern = rng.randint(1, max(2, cfg.vocab_size - 124), size=period)
    seq = np.tile(pattern, seq_len // period + 2)
    toks = jnp.asarray(
        np.stack([np.roll(seq, -i)[: seq_len + 1] for i in range(batch)]),
        jnp.int32,
    )
    data = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(cfg, None, p, data)[0]
        )(p)
        # plain SGD with an fp32 update (bf16 params round-trip per step)
        return jax.tree.map(
            lambda a, b: (a.astype(jnp.float32) - lr * b.astype(jnp.float32))
            .astype(a.dtype),
            p, g,
        ), loss

    for _ in range(steps):
        params, _ = step(params)
    return params, pattern.astype(np.int32)
