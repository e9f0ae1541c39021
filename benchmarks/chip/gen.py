"""Weights and inputs of a benchmark run, made from ``--seed``.

This module is the benchmark's own and imports nothing of the program: the
plain reference (``reference.py``) and the harness's adapter to the
program's parameter tree (``weights.py``) both read their values from here,
so the two see the same numbers without the reference ever taking an array
the program made.

Every array is a pure function of (seed, name, layer, expert): one layer's
weights can be made again on their own, which is how the reference runs
layer by layer at widths whose float32 copy would not fit the chip.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02  # published initializer_range of both source configs


@dataclasses.dataclass(frozen=True)
class Dims:
    """The shapes the benchmark makes weights for; read from the
    configuration file, never from the program."""

    d_model: int
    d_ff: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_layers: int
    vocab: int  # the served vocabulary (ids, logits and loss are over it)
    padded_vocab: int  # rows of the embedding and head as stored
    num_experts: int
    top_k: int
    norm: str  # layernorm | rmsnorm
    norm_eps: float
    rope_theta: float
    qkv_bias: bool

    @staticmethod
    def from_config(c: Dict, padded_vocab: int) -> "Dims":
        return Dims(
            d_model=c["hidden_size"], d_ff=c["intermediate_size"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            num_layers=c["num_hidden_layers"], vocab=c["vocab_size"],
            padded_vocab=padded_vocab,
            num_experts=c["upcycle"]["num_experts"],
            top_k=c["upcycle"]["top_k"], norm=c["norm"],
            norm_eps=c.get("layer_norm_eps", c.get("rms_norm_eps")),
            rope_theta=float(c["rope_theta"]),
            qkv_bias=bool(c.get("use_qkv_bias", c.get("attention_bias", False))),
        )


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: the low and high 32 bits are
    folded in one after the other, so seeds past 2**32 stay distinct."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def _key(key: jax.Array, name: str, *idx: int) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    for i in idx:
        k = jax.random.fold_in(k, i)
    return k


def _normal(key, name, shape, std, *idx) -> jax.Array:
    return jax.random.normal(_key(key, name, *idx), shape, jnp.float32) * std


def top_weights(key: jax.Array, d: Dims) -> Dict[str, jax.Array]:
    """Embedding, head and final norm, float32 as the program stores them."""
    out = {
        "embedding": _normal(key, "embedding", (d.padded_vocab, d.d_model), INIT_STD),
        "unembedding": _normal(key, "unembedding", (d.padded_vocab, d.d_model), INIT_STD),
        "final_norm.scale": 1.0 + _normal(key, "final_norm.scale", (d.d_model,), 0.05),
    }
    if d.norm == "layernorm":
        out["final_norm.bias"] = _normal(key, "final_norm.bias", (d.d_model,), INIT_STD)
    return out


def bf16(x: jax.Array) -> jax.Array:
    """The value a bfloat16 weight holds, as float32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def layer_weights(key: jax.Array, d: Dims, layer: int) -> Dict[str, jax.Array]:
    """One dense layer plus its router, float32 values. Matrices and biases
    are rounded to bfloat16 (the served type); norms and the router stay
    float32, as the program keeps them."""
    D, H, KV, hd, F, E = (d.d_model, d.num_heads, d.num_kv_heads, d.head_dim,
                          d.d_ff, d.num_experts)
    n = lambda name, shape, std: _normal(key, name, shape, std, layer)
    out = {
        "norm1.scale": 1.0 + n("norm1.scale", (D,), 0.05),
        "norm2.scale": 1.0 + n("norm2.scale", (D,), 0.05),
        "wq": bf16(n("wq", (D, H, hd), INIT_STD)),
        "wk": bf16(n("wk", (D, KV, hd), INIT_STD)),
        "wv": bf16(n("wv", (D, KV, hd), INIT_STD)),
        "wo": bf16(n("wo", (H, hd, D), INIT_STD)),
        "w_gate": bf16(n("w_gate", (D, F), INIT_STD)),
        "w_up": bf16(n("w_up", (D, F), INIT_STD)),
        "w_down": bf16(n("w_down", (F, D), INIT_STD)),
        "router": n("router", (D, E), 1.0 / math.sqrt(D)),
    }
    if d.norm == "layernorm":
        out["norm1.bias"] = n("norm1.bias", (D,), INIT_STD)
        out["norm2.bias"] = n("norm2.bias", (D,), INIT_STD)
    if d.qkv_bias:
        out["bq"] = bf16(n("bq", (H, hd), INIT_STD))
        out["bk"] = bf16(n("bk", (KV, hd), INIT_STD))
        out["bv"] = bf16(n("bv", (KV, hd), INIT_STD))
    return out


def expert_weights(key: jax.Array, d: Dims, layer: int) -> Dict[str, jax.Array]:
    """The (E, ...) expert FFN of one freshly upcycled layer: every expert
    is the dense FFN."""
    w = layer_weights(key, d, layer)
    return {name: jnp.broadcast_to(w[name], (d.num_experts,) + w[name].shape)
            for name in ("w_gate", "w_up", "w_down")}


# ---------------------------------------------------------------------------
# Token ids: Zipf-skewed, so that routing is uneven (the idea of the
# program's data/pipeline.py SyntheticSource, made here from the seed)
# ---------------------------------------------------------------------------


def zipf_probs(vocab: int, a: float) -> np.ndarray:
    p = np.arange(1, vocab + 1, dtype=np.float64) ** (-a)
    return p / p.sum()


def zipf_tokens(rng: np.random.Generator, probs: np.ndarray, perm: np.ndarray,
                shape: Tuple[int, ...]) -> np.ndarray:
    """Ids drawn by rank from ``probs``, ranks mapped to ids by ``perm``."""
    ranks = rng.choice(len(probs), size=shape, p=probs)
    return perm[ranks].astype(np.int32)


def host_rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), zlib.crc32(stream.encode())])
