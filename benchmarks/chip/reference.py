"""Plain float32 reference of the upcycled decoder, in ``jax.numpy``.

It imports nothing of the program and takes no array the program made: its
weights come from ``gen.py`` by the seed. Every matrix product runs at
``Precision.HIGHEST`` (on a TPU a float32 product is otherwise rounded to
bfloat16). The equations are the configuration's:

* pre-norm blocks, LayerNorm (scale, bias) or RMSNorm (scale), eps from the
  configuration; rotary embedding over the whole head, the two halves
  rotated as pairs (i, i + hd/2); grouped-query causal attention with
  optional QKV biases; scale 1/sqrt(hd);
* a Mixtral-type router: the top-k of the float32 logits, a softmax over
  those k; each expert a SwiGLU FFN; the output the gate-weighted sum;
* router losses: Switch load balance E * sum(f * p) over the top-k
  assignment fractions f and the mean probabilities p, times 1e-2, and the
  z-loss mean(logsumexp^2) times 1e-3, summed over layers;
* LM head over the stored (padded) vocabulary, whose padded rows take part
  in the softmax's partition function; cross-entropy over next tokens;
* AdamW as the configuration states it: global-norm clipping, bias
  correction, decoupled weight decay on per-layer matrices only.

Experts are computed densely, every expert over every token with its
routing weight (zero where not routed): wasteful, and plain. Attention
runs in query blocks, and the layers and blocks recompute under
``jax.checkpoint`` in the backward pass, so a 4k-token step fits the chip.

``rounding="fp8"`` rounds both operands of every product of the model's
layers (not the float32 router) to float8_e4m3 with one scale per tensor:
the control that a lower precision than the configuration states has to
fail the comparison.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import gen

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
LB_COEF, Z_COEF = 1e-2, 1e-3
Q_BLOCK = 512


def rounder(rounding: str) -> Callable[[jax.Array], jax.Array]:
    if rounding == "f32":
        return lambda x: x
    if rounding == "fp8":
        def q(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
            return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
        return q
    raise ValueError(f"unknown rounding {rounding!r}")


def mm(eq: str, a, b, q) -> jax.Array:
    return jnp.einsum(eq, q(a), q(b), precision=HI, preferred_element_type=F32)


def norm(x, w, prefix: str, d: gen.Dims) -> jax.Array:
    if d.norm == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return ((x - mu) * jax.lax.rsqrt(var + d.norm_eps) * w[prefix + ".scale"]
                + w[prefix + ".bias"])
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + d.norm_eps) * w[prefix + ".scale"]


def rope(x, pos, theta: float) -> jax.Array:
    """x (N, heads, hd); pos (N,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(qh, kh, vh, pos, seg, q) -> jax.Array:
    """Causal attention within each segment. qh (N, H, hd), kh/vh (N, KV,
    hd); pos/seg (N,). Query blocks of Q_BLOCK, each recomputed in the
    backward pass."""
    N, H, hd = qh.shape
    G = H // kh.shape[1]
    kh = jnp.repeat(kh, G, axis=1)
    vh = jnp.repeat(vh, G, axis=1)
    nb = -(-N // Q_BLOCK)
    pad = nb * Q_BLOCK - N
    qp = jnp.pad(qh, ((0, pad), (0, 0), (0, 0))).reshape(nb, Q_BLOCK, H, hd)
    posq = jnp.pad(pos, (0, pad)).reshape(nb, Q_BLOCK)
    segq = jnp.pad(seg, (0, pad), constant_values=-1).reshape(nb, Q_BLOCK)
    scale = 1.0 / math.sqrt(hd)

    @jax.checkpoint
    def one_block(args):
        qb, pq, sq = args
        s = mm("qhd,khd->hqk", qb, kh, q) * scale
        ok = (sq[:, None] == seg[None, :]) & (pos[None, :] <= pq[:, None])
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        return mm("hqk,khd->qhd", p, vh, q)

    out = jax.lax.map(one_block, (qp, posq, segq))
    return out.reshape(nb * Q_BLOCK, H, hd)[:N]


def moe(h, w, d: gen.Dims, q) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """h (N, D) -> (y, load-balance loss, z-loss); the experts are the
    stacked ``experts.*`` arrays of ``w``."""
    E, k = d.num_experts, d.top_k
    logits = jnp.einsum("nd,de->ne", h, w["router"], precision=HI)
    top, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)
    onehot = jax.nn.one_hot(idx, E, dtype=F32)  # (N, k, E)
    combine = jnp.einsum("nk,nke->ne", gates, onehot)
    probs = jax.nn.softmax(logits, axis=-1)
    f = jnp.mean(jnp.sum(onehot, axis=1), axis=0) / k
    lb = E * jnp.sum(f * jnp.mean(probs, axis=0)) * LB_COEF
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))) * Z_COEF

    def ffn(acc, c, wg, wu, wd):
        g = mm("nd,df->nf", h, wg, q)
        u = mm("nd,df->nf", h, wu, q)
        y = mm("nf,fd->nd", jax.nn.silu(g) * u, wd, q)
        return acc + c[:, None] * y

    body = jax.checkpoint(lambda acc, xs: (ffn(acc, xs[3], *xs[:3]), None))
    xs = (w["experts.w_gate"], w["experts.w_up"], w["experts.w_down"], combine.T)
    y, _ = jax.lax.scan(body, jnp.zeros_like(h), xs)
    return y, lb, z


def block(w, x, pos, seg, d: gen.Dims, q) -> Tuple[jax.Array, jax.Array, jax.Array]:
    h = norm(x, w, "norm1", d)
    qh = mm("nd,dhk->nhk", h, w["wq"], q)
    kh = mm("nd,dhk->nhk", h, w["wk"], q)
    vh = mm("nd,dhk->nhk", h, w["wv"], q)
    if d.qkv_bias:
        qh, kh, vh = qh + w["bq"], kh + w["bk"], vh + w["bv"]
    qh, kh = rope(qh, pos, d.rope_theta), rope(kh, pos, d.rope_theta)
    o = attention(qh, kh, vh, pos, seg, q)
    x = x + mm("nhk,hkd->nd", o, w["wo"], q)
    y, lb, z = moe(norm(x, w, "norm2", d), w, d, q)
    return x + y, lb, z


def upcycled_layer(key, d: gen.Dims, layer: int) -> Dict[str, jax.Array]:
    """One layer as training starts: the dense layer's weights, its FFN
    copied into every expert."""
    w = gen.layer_weights(key, d, layer)
    for name, v in gen.expert_weights(key, d, layer).items():
        w["experts." + name] = v
    for name in ("w_gate", "w_up", "w_down"):
        del w[name]
    return w


def init_weights(key, d: gen.Dims) -> Dict:
    return {"top": gen.top_weights(key, d),
            "layers": [upcycled_layer(key, d, l) for l in range(d.num_layers)]}


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def loss(weights, tokens, labels, d: gen.Dims, rounding: str = "f32",
         loss_tokens: Optional[int] = None) -> Tuple[jax.Array, Dict]:
    """Mean next-token loss of one batch (B, S) plus the router losses.
    ``loss_tokens`` takes the mean over the first that many positions of
    each row only (a fault: part of the batch left out)."""
    q = rounder(rounding)
    B, S = tokens.shape
    top = weights["top"]
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)
    seg = jnp.repeat(jnp.arange(B, dtype=jnp.int32), S)
    x = top["embedding"][tokens.reshape(-1)]
    lb_sum = z_sum = jnp.zeros((), F32)
    layer = jax.checkpoint(lambda w, x: block(w, x, pos, seg, d, q))
    for w in weights["layers"]:
        x, lb, z = layer(w, x)
        lb_sum, z_sum = lb_sum + lb, z_sum + z
    x = norm(x, top, "final_norm", d)
    logits = mm("nd,vd->nv", x, top["unembedding"], q)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels.reshape(-1, 1), axis=-1)[:, 0]
    per = (lse - ll).reshape(B, S)
    if loss_tokens is not None:
        per = per[:, :loss_tokens]
    ce = jnp.mean(per)
    return ce + lb_sum + z_sum, {"ce": ce, "lb": lb_sum, "z": z_sum}


def decayed(name: str, value: jax.Array) -> bool:
    """Weight decay on matrices only: a per-layer array of two or more dims
    that is not a norm parameter."""
    return value.ndim >= 2 and "norm" not in name


def adamw(weights, grads, m, v, step, lr, tc: Dict) -> Tuple:
    """One AdamW update (``step`` counts from 1, ``lr`` its learning rate)
    with global-norm clipping; returns (weights, m, v, clipped grads)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves) + 1e-16)
    clip = jnp.minimum(1.0, tc["grad_clip"] / gnorm)
    b1, b2 = tc["adam_b1"], tc["adam_b2"]
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step

    def upd(path, w, g, m_, v_):
        name = jax.tree_util.keystr(path)
        g = g * clip
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * jnp.square(g)
        delta = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + tc["adam_eps"])
        wd = tc["weight_decay"] if decayed(name, w) else 0.0
        return w - lr * (delta + wd * w), m_, v_, g

    out = jax.tree_util.tree_map_with_path(upd, weights, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def lr_at(step: int, tc: Dict) -> float:
    """Warmup to ``lr`` over ``warmup_steps`` (the first update already at
    lr / warmup), then cosine to ``lr_min`` by ``total_steps``."""
    if step < tc["warmup_steps"]:
        return tc["lr"] * min(step + 1, tc["warmup_steps"]) / max(tc["warmup_steps"], 1)
    frac = (step - tc["warmup_steps"]) / max(tc["total_steps"] - tc["warmup_steps"], 1)
    frac = min(max(frac, 0.0), 1.0)
    return tc["lr_min"] + 0.5 * (tc["lr"] - tc["lr_min"]) * (1 + math.cos(math.pi * frac))


def leaf_sq_norms(tree) -> Dict[str, jax.Array]:
    """Squared norms by canonical leaf name, summed over layers: the
    program stacks its layers into one array per name."""
    out: Dict[str, jax.Array] = {}
    for name, v in tree["top"].items():
        out[name] = jnp.sum(jnp.square(v))
    for w in tree["layers"]:
        for name, v in w.items():
            out[name] = out.get(name, 0.0) + jnp.sum(jnp.square(v))
    return out


def train_steps(seed: int, d: gen.Dims, batches: Sequence[Dict[str, np.ndarray]],
                tc: Dict, rounding: str = "f32", loss_tokens: Optional[int] = None
                ) -> Dict[str, object]:
    """Follow the first len(batches) steps of training from the seed's
    weights. Returns the loss of each step, the per-leaf norms of the first
    gradient as the optimizer takes it (clipped), and the per-leaf norms of
    the weights' change over all the steps."""
    key = gen.seed_key(seed)
    weights = jax.jit(lambda k: init_weights(k, d))(key)
    m = jax.tree.map(jnp.zeros_like, weights)
    v = jax.tree.map(jnp.zeros_like, weights)

    @jax.jit
    def grad_step(weights, tokens, labels):
        (l, _), g = jax.value_and_grad(
            lambda w: loss(w, tokens, labels, d, rounding, loss_tokens), has_aux=True
        )(weights)
        return l, g

    upd = jax.jit(lambda w, g, m, v, t, lr: adamw(w, g, m, v, t, lr, tc),
                  donate_argnums=(0, 1, 2, 3))
    sq = jax.jit(leaf_sq_norms)
    losses, g1 = [], {}
    for t, b in enumerate(batches, start=1):
        l, g = grad_step(weights, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
        weights, m, v, gc = upd(weights, g, m, v, jnp.float32(t),
                                jnp.float32(lr_at(t - 1, tc)))
        losses.append(float(l))
        if t == 1:
            g1 = {k: math.sqrt(float(x)) for k, x in jax.device_get(sq(gc)).items()}
        del g, gc
    del m, v
    delta = jax.jit(lambda w, k: leaf_sq_norms(
        jax.tree.map(lambda a, b: a - b, w, init_weights(k, d))))(weights, key)
    del weights
    return {
        "losses": losses,
        "grad_norms": g1,
        "delta_norms": {k: math.sqrt(float(x)) for k, x in jax.device_get(delta).items()},
    }
