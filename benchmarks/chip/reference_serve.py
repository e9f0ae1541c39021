"""Plain float32 reference logits of the served upcycled decoder.

It imports nothing of the program and takes no array the program made:
every weight is made again from the seed, layer by layer and expert by
expert (``gen.py``, ``serve_gen.py``), so the reference fits the chip
beside nothing at the published widths. The equations are
``reference.py``'s (pre-norm blocks, RMSNorm or LayerNorm, rotary
embedding, grouped-query causal attention with QKV biases, the Mixtral
router's top-k softmax gates), with each expert its own diverged SwiGLU
FFN, and every product at ``Precision.HIGHEST``.

Several sequences run as one stream of tokens, each its own segment of
the causal mask; the stream is padded to a multiple of ``N_BLOCK`` tokens
so that a few compiled shapes serve every run. ``rounding="fp8"`` rounds
both operands of every product but the float32 router's to float8_e4m3
(``reference.rounder``): the control one precision below the served
bfloat16.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import gen, reference, serve_gen

N_BLOCK = 4096  # the token stream is padded to a multiple of this
W_BLOCK = 512  # the rows whose logits are read, padded to a multiple of this
F32 = jnp.float32


def _pad(n: int, m: int) -> int:
    return -(-max(n, 1) // m) * m


def _attention_part(w, x, pos, seg, d: gen.Dims, q):
    h = reference.norm(x, w, "norm1", d)
    qh = reference.mm("nd,dhk->nhk", h, w["wq"], q)
    kh = reference.mm("nd,dhk->nhk", h, w["wk"], q)
    vh = reference.mm("nd,dhk->nhk", h, w["wv"], q)
    if d.qkv_bias:
        qh, kh, vh = qh + w["bq"], kh + w["bk"], vh + w["bv"]
    qh, kh = reference.rope(qh, pos, d.rope_theta), reference.rope(kh, pos, d.rope_theta)
    o = reference.attention(qh, kh, vh, pos, seg, q)
    return x + reference.mm("nhk,hkd->nd", o, w["wo"], q)


def layer(key, layer_idx, x, pos, seg, d: gen.Dims, std: float, rounding: str):
    """One block over the token stream ``x`` (N, D): attention, then the
    routed experts, each made from the seed in turn and applied to every
    token with its gate (zero where the token is not routed to it)."""
    q = reference.rounder(rounding)
    w = gen.layer_weights(key, d, layer_idx)
    x = _attention_part(w, x, pos, seg, d, q)
    h = reference.norm(x, w, "norm2", d)
    logits = jnp.einsum("nd,de->ne", h, w["router"], precision=reference.HI)
    top, idx = jax.lax.top_k(logits, d.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    combine = jnp.einsum("nk,nke->ne", gates,
                         jax.nn.one_hot(idx, d.num_experts, dtype=F32))

    def expert(acc, e):
        wg, wu, wd = (serve_gen.diverged(w[n], key, d, layer_idx, e, n, std)
                      for n in serve_gen.EXPERT_LEAVES)
        g = reference.mm("nd,df->nf", h, wg, q)
        u = reference.mm("nd,df->nf", h, wu, q)
        y = reference.mm("nf,fd->nd", jax.nn.silu(g) * u, wd, q)
        return acc + combine[:, e][:, None] * y, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(d.num_experts))
    return x + y


def stream(seqs: Sequence[np.ndarray], want: Sequence[Sequence[int]]):
    """The sequences as one padded stream: tokens, positions, segments
    (padding is segment -1) and the stream index of each wanted position,
    the wanted rows padded to a multiple of ``W_BLOCK``."""
    n = sum(len(s) for s in seqs)
    N = _pad(n, N_BLOCK)
    tokens = np.zeros(N, np.int32)
    pos = np.zeros(N, np.int32)
    seg = np.full(N, -1, np.int32)
    rows: List[int] = []
    at = 0
    for i, (s, wv) in enumerate(zip(seqs, want)):
        tokens[at:at + len(s)] = s
        pos[at:at + len(s)] = np.arange(len(s))
        seg[at:at + len(s)] = i
        rows += [at + int(p) for p in wv]
        at += len(s)
    rows_p = np.zeros(_pad(len(rows), W_BLOCK), np.int32)
    rows_p[:len(rows)] = rows
    return tokens, pos, seg, rows_p, len(rows)


def logits(seed: int, d: gen.Dims, std: float, seqs: Sequence[np.ndarray],
           want: Sequence[Sequence[int]], rounding: str = "f32") -> jax.Array:
    """Logits over the served vocabulary (float32, on the device) at the
    ``want`` positions of each of ``seqs``, in that order: position p's
    logits predict token p + 1."""
    key = gen.seed_key(seed)
    tokens, pos, seg, rows, n = stream(seqs, want)
    x = _embed(key, jnp.asarray(tokens), d)
    pos, seg = jnp.asarray(pos), jnp.asarray(seg)
    for l in range(d.num_layers):
        x = _layer(key, jnp.int32(l), x, pos, seg, d, std, rounding)
    return _head(key, x, jnp.asarray(rows), d, rounding)[:n]


_embed = jax.jit(lambda key, tokens, d: gen.top_weights(key, d)["embedding"][tokens],
                 static_argnums=(2,))
_layer = jax.jit(layer, static_argnums=(5, 6, 7), donate_argnums=(2,))


def _head_impl(key, x, rows, d: gen.Dims, rounding: str):
    top = gen.top_weights(key, d)
    h = reference.norm(x[rows], top, "final_norm", d)
    out = reference.mm("nd,vd->nv", h, top["unembedding"], reference.rounder(rounding))
    return out[:, :d.vocab]


_head = jax.jit(_head_impl, static_argnums=(3, 4))


def served_gaps(ref: jax.Array, served: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at its position."""
    s = jnp.asarray(served, jnp.int32)
    got = jnp.take_along_axis(ref, s[:, None], axis=1)[:, 0]
    return np.asarray(jax.device_get(jnp.max(ref, axis=1) - got), np.float64)


def first_choice_gaps(ref: jax.Array, other: jax.Array) -> np.ndarray:
    """The reference gap of the token that ``other`` puts first at each
    position (the control's reading: no decoding needed)."""
    return served_gaps(ref, np.asarray(jax.device_get(jnp.argmax(other, axis=1))))


def rel_l2(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: ||prog - ref|| / ||ref||."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    return np.linalg.norm(prog - ref, axis=1) / np.linalg.norm(ref, axis=1)


def gap_numbers(gaps: np.ndarray) -> Dict[str, float]:
    """The served-token numbers: the mean and the widest gap over every
    served token of the sample, and how many there were."""
    return {"served_gap_mean": float(gaps.mean()) if gaps.size else float("nan"),
            "served_gap_max": float(gaps.max()) if gaps.size else float("nan"),
            "served_tokens": int(gaps.size)}
