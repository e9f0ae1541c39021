"""Low-precision serving: int8 expert weights + int8 KV pages.

Covers the error-budget contract from core/quant.py:

* int8 kernels vs their quantized oracles (exact rewrite — tight parity);
* quantized vs bf16 model logits within the published budgets on the e8t2
  smoke config;
* EXACT greedy-token parity over a short decode, on a sharpened probe
  model (random-init logits are near-uniform, so token parity there is a
  coin flip — see quant.sharpen_for_parity);
* the PagePool scale sidecar can never desync from its page payload
  across alloc / COW / defrag / free (property test);
* int8-aware tile sizing in the Pallas block picker;
* engine/config validation of the quant modes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, smoke_config
from repro.core.quant import (
    INT8_KV_LOGIT_BUDGET,
    INT8_LOGIT_BUDGET,
    KERNEL_PARITY_TOL,
    dequantize_kv,
    dequantize_weight,
    quantize_experts,
    quantize_kv,
    quantize_params,
    quantize_weight,
    sharpen_for_parity,
)
from repro.models.model import forward, model_decl
from repro.serving.engine import Request, ServingEngine
from repro.sharding.rules import init_from_decls


def _e8t2():
    cfg = smoke_config(get_config("llama3-e8t2"))
    # dropless + the single-host dispatcher (alltoall needs an EP plan and
    # would trip REPRO_STRICT_DISPATCH)
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, capacity_factor=None, dispatcher="allgather"))


# -- quantizer round trips ----------------------------------------------------


def test_quantize_weight_roundtrip():
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.standard_normal((4, 64, 96)), jnp.bfloat16) * 0.05
    q, s = quantize_weight(w)
    assert q.dtype == jnp.int8 and s.dtype == jnp.bfloat16
    assert s.shape == (4, 96)
    err = jnp.max(
        jnp.abs(dequantize_weight(q, s) - w.astype(jnp.float32)), axis=-2)
    # per channel: half a quantization step, plus up to 127 steps' worth of
    # the bf16 scale's half-ulp relative rounding (7 mantissa bits -> 2^-8),
    # and one more 2^-8 factor because the bound is stated in the *rounded*
    # scale -- together just under one full step
    bound = s.astype(jnp.float32) * (0.5 + 127 * 2.0**-8) * (1 + 2.0**-8)
    assert bool(jnp.all(err <= bound)), float(jnp.max(err - bound))


def test_quantize_kv_roundtrip():
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal((3, 8, 2, 64)), jnp.bfloat16) * 0.3
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (3, 8, 2, 1)
    err = jnp.max(
        jnp.abs(dequantize_kv(q, s) - x.astype(jnp.float32)),
        axis=-1, keepdims=True)
    # f32 scales: half a step per (token, head) vector, tiny rounding slack
    assert bool(jnp.all(err <= s * 0.51)), float(jnp.max(err - s * 0.51))


def test_quantize_experts_idempotent(rng):
    experts = {
        "w_gate": jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.bfloat16),
        "w_up": jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.bfloat16),
        "w_down": jnp.asarray(rng.standard_normal((2, 32, 16)), jnp.bfloat16),
    }
    q = quantize_experts(experts)
    assert q["w_gate"].dtype == jnp.int8 and "w_down_scale" in q
    assert quantize_experts(q) is q  # second pass is a no-op


# -- kernel vs quantized oracle ----------------------------------------------


def _quant_ffn(rng, E=4, D=128, F=256):
    wg = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
    wu = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.05
    wd = jnp.asarray(rng.standard_normal((E, F, D)), jnp.bfloat16) * 0.05
    (qg, sg), (qu, su), (qd, sd) = map(quantize_weight, (wg, wu, wd))
    return qg, qu, qd, sg, su, sd


def test_expert_gemm_q8_matches_oracle(rng):
    from repro.kernels.ops import expert_gemm_q8
    from repro.kernels.ref import expert_gemm_q8_ref

    E, C, D, F = 4, 64, 128, 256
    xe = jnp.asarray(rng.standard_normal((E, C, D)), jnp.bfloat16) * 0.3
    qargs = _quant_ffn(rng, E, D, F)
    y = expert_gemm_q8(xe, *qargs)
    ref = expert_gemm_q8_ref(xe, *qargs)
    err = float(jnp.max(jnp.abs(
        y.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err <= KERNEL_PARITY_TOL, err


def test_grouped_gemm_q8_matches_oracle(rng):
    from repro.kernels.ops import grouped_gemm_q8
    from repro.kernels.ref import grouped_gemm_q8_ref

    E, D, F, N = 4, 128, 256, 512
    xs = jnp.asarray(rng.standard_normal((N, D)), jnp.bfloat16) * 0.3
    gs = jnp.full((E,), N // E, jnp.int32)
    qargs = _quant_ffn(rng, E, D, F)
    y = grouped_gemm_q8(xs, *qargs, gs, row_block=128)
    ref = grouped_gemm_q8_ref(xs, *qargs, gs)
    err = float(jnp.max(jnp.abs(
        y.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err <= KERNEL_PARITY_TOL, err


def test_paged_attention_q8_matches_oracle(rng):
    from repro.kernels.ops import paged_attention_q8
    from repro.kernels.ref import paged_attention_q8_ref

    P, ps, B, H, KV, d, maxP = 16, 8, 3, 4, 2, 64, 4
    kq, ks = quantize_kv(
        jnp.asarray(rng.standard_normal((P, KV, ps, d)), jnp.bfloat16) * 0.3)
    vq, vs = quantize_kv(
        jnp.asarray(rng.standard_normal((P, KV, ps, d)), jnp.bfloat16) * 0.3)
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.bfloat16) * 0.3
    bt = jnp.asarray(rng.permutation(P)[: B * maxP].reshape(B, maxP), jnp.int32)
    sl = jnp.asarray(rng.integers(1, maxP * ps, B), jnp.int32)
    y = paged_attention_q8(q, kq, vq, ks, vs, bt, sl)
    ref = paged_attention_q8_ref(q, kq, vq, ks, vs, bt, sl)
    err = float(jnp.max(jnp.abs(
        y.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err <= KERNEL_PARITY_TOL, err


# -- model-level logit budgets ------------------------------------------------


def test_quant_weights_logit_budget():
    # own generator: the shared session rng's state depends on which tests
    # ran first, and this budget is a measurement, not an exact property
    rng = np.random.default_rng(7)
    cfg = _e8t2()
    params = init_from_decls(model_decl(cfg), jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)}
    base, _ = forward(cfg, None, params, batch)
    quant, _ = forward(cfg, None, quantize_params(params), batch)
    err = float(jnp.max(jnp.abs(
        base.astype(jnp.float32) - quant.astype(jnp.float32))))
    assert err <= INT8_LOGIT_BUDGET, err


def test_quant_kv_logit_budget(sharpened):
    """Prefill through the cache-bearing forward with bf16 vs int8 pages:
    per-position logits must agree within the KV budget (both pool
    variants use the same page-table view; the Pallas decode kernel's read
    path is covered by the oracle test above). Measured on the sharpened
    probe with an in-distribution prompt: a random-init model's router
    sits at near-ties, so the tiny KV perturbation flips top-k expert
    choices and the logit delta measures routing luck, not dequant error
    (observed 0.36-0.45 across seeds vs a stable ~0.14 here)."""
    from repro.models.model import paged_forward
    from repro.serving.kv_cache import init_paged_pool

    cfg, params, pattern = sharpened
    toks = jnp.asarray(pattern[None, :24], jnp.int32)
    out = {}
    for tag, quant in (("bf16", "none"), ("int8", "int8")):
        qcfg = cfg.replace(quant_kv=quant)
        pool = init_paged_pool(qcfg, 7, 8)  # 7 usable + trailing trash page
        lg, _ = paged_forward(
            qcfg, None, params, pool, toks,
            pos_start=jnp.zeros((1,), jnp.int32),
            page_table=jnp.asarray([[0, 1, 2, -1]], jnp.int32),
            valid_len=jnp.asarray([24], jnp.int32),
            return_all_logits=True,
        )
        out[tag] = lg.astype(jnp.float32)
    err = float(jnp.max(jnp.abs(out["bf16"] - out["int8"])))
    assert err <= INT8_KV_LOGIT_BUDGET, err
    assert jnp.array_equal(out["bf16"].argmax(-1), out["int8"].argmax(-1))


# -- greedy-token parity on the sharpened probe -------------------------------


@pytest.fixture(scope="module")
def sharpened():
    cfg = _e8t2()
    params = init_from_decls(model_decl(cfg), jax.random.PRNGKey(0))
    params, pattern = sharpen_for_parity(cfg, params)
    return cfg, params, pattern


def _probe_requests(pattern, n=4, prompt_len=24, new=8):
    return [
        Request(rid=i,
                prompt=np.roll(pattern, -i)[:prompt_len].astype(np.int32),
                max_new_tokens=new)
        for i in range(n)
    ]


@pytest.mark.parametrize("quant", [
    dict(quant_weights="int8"),
    dict(quant_kv="int8"),
    dict(quant_weights="int8", quant_kv="int8"),
])
def test_greedy_parity_sharpened(sharpened, quant):
    """EXACT greedy-token parity: on the probe model the top-1 margins
    (~4.7) dwarf the int8 logit error (~0.04), so any token flip is a real
    quantization bug, not noise."""
    cfg, params, pattern = sharpened
    kw = dict(max_batch=4, max_seq=64, cache_mode="paged", page_size=8,
              prefill_chunk=16)
    base = ServingEngine(cfg, params, **kw)
    out_base = base.run(_probe_requests(pattern))
    eng = ServingEngine(cfg, params, **kw, **quant)
    out_q = eng.run(_probe_requests(pattern))
    assert out_base == out_q, {
        rid: (out_base[rid], out_q[rid])
        for rid in out_base if out_base[rid] != out_q[rid]
    }
    eng.page_pool.check_invariants()
    assert eng.page_pool.free_pages == eng.page_pool.num_pages


# -- sidecar/payload no-desync property ---------------------------------------


def _apply_pool_ops(pool, ops):
    from repro.serving.kv_cache import copy_pages, permute_pool

    for kind, a, b in ops:
        if kind == "copy" and a != b:
            pool = copy_pages(pool, [(a, b)])
        elif kind == "permute" and a != b:
            # a legal defrag mapping is a permutation: swap a <-> b
            pool = permute_pool(pool, {a: b, b: a})
    return pool


def _check_sidecar_sync(ops):
    """Fill every payload entry of page p with the constant p and its
    sidecar scale likewise; apply an arbitrary COW/defrag sequence through
    the real pool-tree operators. Because the sidecar is a pool leaf, the
    page-id pattern must stay identical across payload and sidecar — any
    structural divergence (a future op touching only k/v) desyncs the
    constants and fails here."""
    from conftest import tiny_dense
    from repro.serving.kv_cache import init_paged_pool

    cfg = tiny_dense(num_layers=1).replace(quant_kv="int8")
    pool = init_paged_pool(cfg, 8, 4)
    n = jax.tree.leaves(pool)[0].shape[1]
    ids = jnp.arange(n)
    pool = jax.tree.map(
        lambda a: jnp.broadcast_to(
            ids.reshape(1, n, 1, 1, 1), a.shape
        ).astype(a.dtype),
        pool,
    )
    pool = _apply_pool_ops(pool, ops)
    leaves = jax.tree.leaves(pool)
    ref = leaves[0][0, :, 0, 0, 0].astype(jnp.int32)
    for leaf in leaves[1:]:
        got = leaf[0, :, 0, 0, 0].astype(jnp.int32)
        assert jnp.array_equal(ref, got), (ref, got)


def test_pool_sidecar_never_desyncs_seeded():
    """Deterministic fallback for environments without hypothesis: 20
    seeded random COW/defrag sequences through the same checker."""
    rng = np.random.default_rng(42)
    for _ in range(20):
        ops = [
            (("copy", "permute")[int(rng.integers(2))],
             int(rng.integers(8)), int(rng.integers(8)))
            for _ in range(int(rng.integers(1, 12)))
        ]
        _check_sidecar_sync(ops)


def test_pool_sidecar_never_desyncs_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    _op = st.one_of(
        st.tuples(st.just("copy"), st.integers(0, 7), st.integers(0, 7)),
        st.tuples(st.just("permute"), st.integers(0, 7), st.integers(0, 7)),
    )

    @settings(max_examples=30, deadline=None)
    @given(ops=st.lists(_op, max_size=12))
    def run(ops):
        _check_sidecar_sync(ops)

    run()


# -- tile sizing --------------------------------------------------------------


def test_pick_scales_with_itemsize():
    from repro.kernels.expert_gemm import _pick

    # int8 operands get twice the rows of the bf16-calibrated budget...
    assert _pick(256, 1024, itemsize=1) == 512
    assert _pick(256, 1024, itemsize=2) == 256
    # ...f32 half, and lane alignment survives the scaling
    assert _pick(256, 1024, itemsize=4) == 128
    for item in (1, 2, 4):
        assert _pick(256, 1024, itemsize=item) % 128 == 0
    with pytest.raises(AssertionError):
        _pick(256, 1024, itemsize=3)
    # misaligned split still asserts regardless of scaling: 192 has no
    # 128-aligned divisor, and the int8-scaled block (128) != whole dim
    with pytest.raises(AssertionError):
        _pick(64, 192, itemsize=1)


# -- validation ---------------------------------------------------------------


def test_engine_quant_validation():
    cfg = _e8t2()
    params = init_from_decls(model_decl(cfg), jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(cfg, params, max_batch=2, max_seq=32,
                      cache_mode="ring", quant_kv="int8")
    with pytest.raises(ValueError):
        ServingEngine(cfg, params, max_batch=2, max_seq=32,
                      quant_weights="int4")


def test_config_quant_validation():
    cfg = _e8t2()
    with pytest.raises(AssertionError):
        cfg.replace(quant_weights="fp8")
    with pytest.raises(AssertionError):
        cfg.replace(quant_kv="int4")
    assert cfg.replace(quant_kv="int8").quant_kv == "int8"
