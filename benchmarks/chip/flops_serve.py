"""Operations and bytes of serving calls, from shapes and the lengths the
calls served (``flops.py`` holds the training counts and the peaks).

Model FLOPs count matrix multiplications as the algorithm needs them: the
projections, router and k routed experts of every real token, attention
over each token's real context (causal: a token at position p attends to
p + 1 keys), and the LM head only where a logit is used (the last token of
a prompt's final chunk, every decoded token). Padding rows, idle decode
slots and unembedded chunk positions count nothing.
"""
from __future__ import annotations

from typing import Dict, Iterable

from . import flops
from .gen import Dims


def layer_token_flops(d: Dims) -> float:
    """One token's FLOPs outside attention's score and value products,
    over all layers: QKV and out projections, router, k experts."""
    D, H, KV, hd, F = d.d_model, d.num_heads, d.num_kv_heads, d.head_dim, d.d_ff
    proj = 2 * D * H * hd + 2 * 2 * D * KV * hd + 2 * H * hd * D
    return d.num_layers * (proj + 2 * D * d.num_experts + d.top_k * 3 * 2 * D * F)


def head_flops(d: Dims) -> float:
    return 2.0 * d.d_model * d.vocab


def attention_flops(d: Dims, keys: float) -> float:
    """Scores and values of one query over ``keys`` keys, all layers."""
    return d.num_layers * 4.0 * d.num_heads * d.head_dim * keys


def chunk_flops(d: Dims, start: int, length: int, final: bool) -> float:
    """A prefill chunk of ``length`` real tokens at positions start ..
    start + length - 1; the head once where the chunk is a prompt's last."""
    keys = length * start + length * (length + 1) / 2  # sum of p + 1
    return (length * layer_token_flops(d) + attention_flops(d, keys)
            + (head_flops(d) if final else 0.0))


def decode_flops(d: Dims, seq_lens: Iterable[int]) -> float:
    """One decode step: each live row one token over its ``seq_len`` keys
    (the new token included)."""
    lens = list(seq_lens)
    return sum(layer_token_flops(d) + head_flops(d) + attention_flops(d, n) for n in lens)


def paged_attention(d: Dims, seq_lens: Iterable[int], page_size: int,
                    kv_bytes: int = 2, q_bytes: int = 2) -> Dict[str, float]:
    """The paged-attention decode kernel of one layer over live rows of
    ``seq_lens`` keys: QK^T and PV for every query head (4 H hd per key);
    the K and V pages each row reads (whole pages, ceil(n / page_size) of
    page_size x KV x hd), its q read and its out written once."""
    H, KV, hd = d.num_heads, d.num_kv_heads, d.head_dim
    lens = list(seq_lens)
    pages = sum(-(-n // page_size) for n in lens)
    return {"flops": 4.0 * H * hd * sum(lens),
            "bytes": 2.0 * pages * page_size * KV * hd * kv_bytes
            + 2.0 * len(lens) * H * hd * q_bytes}


def expert_gemm(d: Dims, tokens: int) -> Dict[str, float]:
    """The grouped-GEMM expert FFN of one layer over ``tokens`` real tokens
    (k routed rows each): gate, up and down products; each used expert's
    three bf16 weights read once (at most min(E, k x tokens) experts are
    used), rows read and written once in bf16."""
    rows = tokens * d.top_k
    used = min(d.num_experts, rows)
    return {"flops": 6.0 * rows * d.d_model * d.d_ff,
            "bytes": used * 3.0 * d.d_model * d.d_ff * 2 + 2.0 * rows * d.d_model * 2}


def window_flops(ctx: Dict) -> float:
    """Model FLOPs of the window's calls (``chunk_flops``, ``decode_flops``)."""
    d = ctx["dims"]
    return (sum(chunk_flops(d, s, n, f) for s, n, f in ctx["chunks"])
            + sum(decode_flops(d, lens) for lens in ctx["decodes"]))


def least_seconds(ctx: Dict, work) -> float:
    """Summed least time of ``work(call)`` over the window's calls, per
    layer, times the layers."""
    return ctx["dims"].num_layers * sum(flops.least_seconds(w, ctx["peaks"]) for w in work)
