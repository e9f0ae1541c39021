"""Operations and bytes from shapes, and the table of peaks.

Model FLOPs count matrix multiplications only, as the algorithm needs them:
an embedding lookup is a gather and counts nothing, recomputation under
remat counts nothing, and causal attention counts the half of the score
matrix that is computed. Training is three times the forward pass.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

from .gen import Dims

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def dense_token_flops(d: Dims) -> float:
    """Forward FLOPs of one token outside attention's score and value
    products: projections, router, the k routed experts and the LM head
    over the served vocabulary, summed over layers."""
    D, H, KV, hd, F = d.d_model, d.num_heads, d.num_kv_heads, d.head_dim, d.d_ff
    proj = 2 * D * H * hd + 2 * 2 * D * KV * hd + 2 * H * hd * D
    router = 2 * D * d.num_experts
    experts = d.top_k * 3 * 2 * D * F
    return d.num_layers * (proj + router + experts) + 2 * D * d.vocab


def train_flops_per_token(d: Dims, seq: int) -> float:
    """Forward + backward (3x forward) FLOPs per token of a causal sequence
    of ``seq`` tokens: the mean key count is (seq + 1) / 2."""
    fwd = dense_token_flops(d) + d.num_layers * 4 * d.num_heads * d.head_dim * (seq + 1) / 2
    return 3 * fwd


def grouped_gemm_fwd(rows: int, experts_used: int, D: int, F: int) -> Dict[str, float]:
    """SwiGLU expert FFN over ``rows`` routed rows (gate and up, then down):
    each used expert's three bf16 weights read once, rows read and written
    once in bf16."""
    return {"flops": 6.0 * rows * D * F,
            "bytes": experts_used * 3.0 * D * F * 2 + 2.0 * rows * D * 2}


def grouped_gemm_bwd(rows: int, experts_used: int, D: int, F: int) -> Dict[str, float]:
    """Its backward: dW for three weights and dx (12 rows*D*F FLOPs, no
    recompute); weights read in bf16, their gradients written in fp32, x and
    dy read and dx written in bf16."""
    return {"flops": 12.0 * rows * D * F,
            "bytes": experts_used * 3.0 * D * F * (2 + 4) + 3.0 * rows * D * 2}


def flash_fwd(B: int, H: int, KV: int, S: int, hd: int) -> Dict[str, float]:
    """Causal attention forward over S tokens: QK^T and PV over the lower
    triangle; q, k, v read and o written once in bf16."""
    return {"flops": 2.0 * 2.0 * B * H * hd * S * (S + 1) / 2,
            "bytes": 2.0 * B * S * hd * (2 * H + 2 * KV)}


def flash_bwd(B: int, H: int, KV: int, S: int, hd: int) -> Dict[str, float]:
    """Its backward: dQ, dK, dV and dP over the lower triangle (twice the
    forward); q, k, v, o, dO read and dq, dk, dv written once."""
    f = flash_fwd(B, H, KV, S, hd)
    return {"flops": 2.0 * f["flops"],
            "bytes": 2.0 * B * S * hd * (4 * H + 4 * KV)}


def least_seconds(work: Dict[str, float], pk: Dict[str, float]) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak HBM
    bandwidth."""
    return max(work["flops"] / pk["bf16_flops_per_s"],
               work["bytes"] / pk["hbm_bytes_per_s"])


def add(*works: Dict[str, float]) -> Dict[str, float]:
    return {"flops": sum(w["flops"] for w in works),
            "bytes": sum(w["bytes"] for w in works)}


def scale(w: Dict[str, float], k: float) -> Dict[str, float]:
    return {"flops": w["flops"] * k, "bytes": w["bytes"] * k}
