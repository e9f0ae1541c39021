"""Paged-attention decode kernel vs the `kernels/ref.py` oracle over
shape / GQA-grouping / page-size sweeps, plus a dense cross-check that the
oracle itself equals ordinary causal attention on a contiguous layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import paged_attention
from repro.kernels.ref import paged_attention_ref


def _scatter_case(rng, B, H, KV, d, ps, maxP, num_pages, lens):
    """Random pool + disjoint per-sequence page lists covering `lens`."""
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((num_pages, KV, ps, d)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((num_pages, KV, ps, d)), jnp.float32)
    perm = rng.permutation(num_pages)
    bt = np.full((B, maxP), -1, np.int32)
    used = 0
    for b in range(B):
        need = -(-int(lens[b]) // ps)
        bt[b, :need] = perm[used : used + need]
        used += need
    return q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("H,KV", [(4, 1), (4, 2), (8, 8), (6, 3)])
@pytest.mark.parametrize("ps", [1, 4, 8])
def test_kernel_matches_ref_gqa_page_sweep(H, KV, ps):
    rng = np.random.default_rng(H * 100 + ps)
    B, d, maxP = 3, 32, 6
    num_pages = B * maxP
    lens = rng.integers(1, maxP * ps + 1, B)
    q, kp, vp, bt, sl = _scatter_case(rng, B, H, KV, d, ps, maxP, num_pages, lens)
    out = paged_attention(q, kp, vp, bt, sl)
    ref = paged_attention_ref(q, kp, vp, bt, sl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("window", [1, 3, 8, 64])
def test_kernel_matches_ref_window(window):
    rng = np.random.default_rng(window)
    B, H, KV, d, ps, maxP = 2, 4, 2, 64, 4, 8
    lens = rng.integers(1, maxP * ps + 1, B)
    q, kp, vp, bt, sl = _scatter_case(rng, B, H, KV, d, ps, maxP, B * maxP, lens)
    out = paged_attention(q, kp, vp, bt, sl, window=window)
    ref = paged_attention_ref(q, kp, vp, bt, sl, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_kernel_skips_released_pages():
    """-1 block-table entries below a window (released pages) change
    nothing: the masked set is identical."""
    rng = np.random.default_rng(0)
    B, H, KV, d, ps, maxP, w = 1, 4, 2, 32, 4, 8, 6
    lens = np.asarray([29])
    q, kp, vp, bt, sl = _scatter_case(rng, B, H, KV, d, ps, maxP, maxP, lens)
    full = paged_attention(q, kp, vp, bt, sl, window=w)
    bt_rel = np.asarray(bt).copy()
    # pages entirely below the window of the current query (pos = len - 1,
    # which masks kpos <= len - 1 - w) are dead
    for j in range(maxP):
        if (j + 1) * ps - 1 <= int(lens[0]) - 1 - w:
            bt_rel[0, j] = -1
    assert (bt_rel == -1).sum() > (np.asarray(bt) == -1).sum()
    rel = paged_attention(q, kp, vp, jnp.asarray(bt_rel), sl, window=w)
    np.testing.assert_allclose(np.asarray(full), np.asarray(rel), atol=1e-6)


def test_ref_matches_dense_attention():
    """Oracle sanity: with an identity page layout the paged ref equals
    plain masked attention over the contiguous KV prefix."""
    rng = np.random.default_rng(3)
    B, H, KV, d, ps, maxP = 2, 8, 2, 32, 4, 4
    S = maxP * ps
    lens = np.asarray([S, S - 5])
    k = rng.standard_normal((B, S, KV, d)).astype(np.float32)
    v = rng.standard_normal((B, S, KV, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    # pool page b*maxP + j holds sequence b's tokens [j*ps, (j+1)*ps)
    k_pool = jnp.asarray(k.reshape(B * maxP, ps, KV, d).transpose(0, 2, 1, 3))
    v_pool = jnp.asarray(v.reshape(B * maxP, ps, KV, d).transpose(0, 2, 1, 3))
    bt = jnp.asarray(np.arange(B * maxP).reshape(B, maxP).astype(np.int32))
    out = paged_attention_ref(q, k_pool, v_pool, bt, jnp.asarray(lens, jnp.int32))

    G = H // KV
    qg = np.asarray(q).reshape(B, KV, G, d)
    want = np.zeros((B, KV, G, d), np.float32)
    for b in range(B):
        n = int(lens[b])
        s = np.einsum("kgd,skd->kgs", qg[b], k[b, :n]) * (d**-0.5)
        p = jax.nn.softmax(jnp.asarray(s), axis=-1)
        want[b] = np.einsum("kgs,skd->kgd", np.asarray(p), v[b, :n])
    np.testing.assert_allclose(
        np.asarray(out), want.reshape(B, H, d), atol=2e-5
    )


def test_kernel_idle_sequence_emits_zeros():
    """A batch slot with no pages (all -1) must produce exact zeros — the
    engine relies on this being well-defined, not NaN."""
    rng = np.random.default_rng(1)
    B, H, KV, d, ps, maxP = 2, 4, 2, 32, 4, 4
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((maxP, KV, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((maxP, KV, ps, d)), jnp.float32)
    bt = np.full((B, maxP), -1, np.int32)
    bt[0, :2] = [0, 1]
    out = paged_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray([5, 0], jnp.int32))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(bt), jnp.asarray([5, 0], jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
