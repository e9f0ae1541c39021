"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import expert_gemm, flash_attention
from repro.kernels.ref import expert_gemm_ref, flash_attention_ref

EG_SHAPES = [  # (E, C, D, F)
    (2, 16, 32, 64),
    (4, 64, 128, 256),
    (8, 128, 64, 128),
    (1, 256, 128, 512),
    (3, 32, 96, 160),  # non-power-of-two dims
]


@pytest.mark.parametrize("shape", EG_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_gemm(rng, shape, dtype):
    E, C, D, F = shape
    xe = jnp.asarray(rng.standard_normal((E, C, D)), dtype) * 0.3
    wg = jnp.asarray(rng.standard_normal((E, D, F)), dtype) * 0.05
    wu = jnp.asarray(rng.standard_normal((E, D, F)), dtype) * 0.05
    wd = jnp.asarray(rng.standard_normal((E, F, D)), dtype) * 0.05
    y = expert_gemm(xe, wg, wu, wd)
    yr = expert_gemm_ref(xe, wg, wu, wd)
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=atol
    )


def test_expert_gemm_group_batched(rng):
    """The (G, E, C, D) layout the MoE dispatcher feeds the kernel."""
    xe = jnp.asarray(rng.standard_normal((3, 4, 16, 32)), jnp.float32) * 0.2
    wg = jnp.asarray(rng.standard_normal((4, 32, 64)), jnp.float32) * 0.1
    wu = jnp.asarray(rng.standard_normal((4, 32, 64)), jnp.float32) * 0.1
    wd = jnp.asarray(rng.standard_normal((4, 64, 32)), jnp.float32) * 0.1
    y = expert_gemm(xe, wg, wu, wd)
    yr = jax.vmap(lambda x: expert_gemm_ref(x, wg, wu, wd))(xe)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-5)


GG_CASES = [  # (E, D, F, group_sizes, row_block)
    (4, 32, 64, (16, 0, 7, 9), 8),
    (2, 64, 128, (128, 128), 128),  # exactly tile-aligned groups
    (3, 96, 160, (1, 50, 13), 16),  # non-power-of-two dims, ragged groups
    (4, 32, 64, (0, 0, 0, 40), 8),  # all tokens on one expert (imbalance)
    (2, 32, 64, (0, 0), 8),  # nothing routed at all
]


@pytest.mark.parametrize("case", GG_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_gemm(rng, case, dtype):
    """Group-size-aware grouped GEMM (sorted dropless layout) vs the
    pure-jnp oracle, interpret mode."""
    from repro.core.dispatch.sorted import aligned_rows
    from repro.kernels.ops import grouped_gemm
    from repro.kernels.ref import grouped_gemm_ref

    E, D, F, gs, bc = case
    gs = np.asarray(gs, np.int32)
    N_pad = aligned_rows(int(gs.sum()), E, bc)
    # build the tile-aligned expert-sorted buffer: valid rows random, padding
    # rows POISONED (not zero) — the kernel must mask them, not rely on zeros
    xs = np.full((N_pad, D), 7.5, np.float32)
    padded = (gs + bc - 1) // bc * bc
    starts = np.cumsum(padded) - padded
    for e in range(E):
        xs[starts[e]:starts[e] + gs[e]] = rng.standard_normal((gs[e], D)) * 0.3
    xs = jnp.asarray(xs, dtype)
    wg = jnp.asarray(rng.standard_normal((E, D, F)), dtype) * 0.05
    wu = jnp.asarray(rng.standard_normal((E, D, F)), dtype) * 0.05
    wd = jnp.asarray(rng.standard_normal((E, F, D)), dtype) * 0.05
    y = grouped_gemm(xs, wg, wu, wd, jnp.asarray(gs), row_block=bc)
    yr = grouped_gemm_ref(xs, wg, wu, wd, jnp.asarray(gs), row_block=bc)
    # compare valid rows; padding rows must come out exactly zero
    valid = np.zeros(N_pad, bool)
    for e in range(E):
        valid[starts[e]:starts[e] + gs[e]] = True
    atol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32)[valid], np.asarray(yr, np.float32)[valid], atol=atol
    )
    np.testing.assert_array_equal(np.asarray(y, np.float32)[~valid], 0.0)


def test_grouped_gemm_matches_padded_expert_gemm(rng):
    """Same tokens through both layouts: flat sorted+group_sizes == dense
    padded (E, C, D) expert_gemm on the populated slots."""
    from repro.kernels.ref import expert_gemm_ref, grouped_gemm_ref

    E, C, D, F = 3, 8, 32, 64
    gs = np.array([8, 3, 5], np.int32)
    xe = np.zeros((E, C, D), np.float32)
    for e in range(E):
        xe[e, : gs[e]] = rng.standard_normal((gs[e], D)) * 0.3
    wg = jnp.asarray(rng.standard_normal((E, D, F)), jnp.float32) * 0.05
    wu = jnp.asarray(rng.standard_normal((E, D, F)), jnp.float32) * 0.05
    wd = jnp.asarray(rng.standard_normal((E, F, D)), jnp.float32) * 0.05
    y_pad = expert_gemm_ref(jnp.asarray(xe), wg, wu, wd)
    xs = np.concatenate([xe[e, : gs[e]] for e in range(E)])
    y_sorted = grouped_gemm_ref(jnp.asarray(xs), wg, wu, wd, jnp.asarray(gs))
    off = 0
    for e in range(E):
        np.testing.assert_allclose(
            np.asarray(y_sorted)[off : off + gs[e]],
            np.asarray(y_pad)[e, : gs[e]],
            atol=1e-5,
        )
        off += gs[e]


# explicit small blocks: several q and k tiles, dead ones among them
FA_TILED_CASES = [  # (B, Sq, Sk, H, KV, d, causal, window, blocks)
    (1, 512, 512, 2, 2, 32, True, None, (128, 128)),  # causal, 4 x 4 tiles
    (1, 128, 512, 2, 1, 32, True, None, (32, 128)),  # right-aligned Sq < Sk
    (1, 256, 256, 2, 2, 32, True, 24, (64, 64)),  # window narrower than a tile
    (1, 256, 256, 2, 2, 32, True, 96, (64, 64)),  # window wider than a tile
    (2, 256, 256, 4, 2, 32, True, None, (64, 128)),  # GQA G 2, bq != bk
    (1, 64, 256, 2, 1, 32, True, 16, (32, 64)),  # k-blocks no query sees
]


FA_CASES = [  # (B, Sq, Sk, H, KV, d, causal, window, blocks)
    (2, 64, 64, 4, 2, 32, True, None, None),
    (1, 32, 128, 4, 4, 64, True, None, None),  # decode-ish: Sq < Sk, right-aligned
    (2, 128, 128, 8, 2, 32, True, 16, None),  # sliding window
    (1, 64, 64, 2, 2, 16, False, None, None),  # encoder (non-causal)
    (2, 1, 256, 4, 1, 64, True, None, None),  # single-token decode, MQA
    (1, 256, 256, 4, 4, 128, True, None, None),  # head_dim 128 (TPU-native)
] + FA_TILED_CASES


@pytest.mark.parametrize("case", FA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(rng, case, dtype):
    B, Sq, Sk, H, KV, d, causal, window, blocks = case
    q = jnp.asarray(rng.standard_normal((B, Sq, H, d)), dtype) * 0.3
    k = jnp.asarray(rng.standard_normal((B, Sk, KV, d)), dtype) * 0.3
    v = jnp.asarray(rng.standard_normal((B, Sk, KV, d)), dtype) * 0.3
    y = flash_attention(q, k, v, causal=causal, window=window, blocks=blocks)
    kb, vb = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
    yr = flash_attention_ref(q, kb, vb, causal=causal, window=window)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=atol
    )


# ---------------------------------------------------------------------------
# Gradient parity: jax.grad through the Pallas custom_vjp vs the ref / XLA
# oracles (interpret mode)
# ---------------------------------------------------------------------------

EG_GRAD_SHAPES = [  # (E, C, D, F): pow2, non-pow2, single-expert
    (2, 16, 32, 64),
    (3, 32, 96, 160),
    (1, 64, 128, 256),
]


@pytest.mark.parametrize("shape", EG_GRAD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_gemm_grad_parity(rng, shape, dtype):
    """jax.grad through the padded Pallas kernel == grad of the ref oracle
    for inputs and all three expert weights."""
    E, C, D, F = shape
    xe = jnp.asarray(rng.standard_normal((E, C, D)), dtype) * 0.3
    wg = jnp.asarray(rng.standard_normal((E, D, F)), dtype) * 0.05
    wu = jnp.asarray(rng.standard_normal((E, D, F)), dtype) * 0.05
    wd = jnp.asarray(rng.standard_normal((E, F, D)), dtype) * 0.05
    r = jnp.asarray(rng.standard_normal((E, C, D)), dtype)

    gk = jax.grad(lambda *a: jnp.sum(expert_gemm(*a) * r), argnums=(0, 1, 2, 3))(
        xe, wg, wu, wd
    )
    gr = jax.grad(lambda *a: jnp.sum(expert_gemm_ref(*a) * r), argnums=(0, 1, 2, 3))(
        xe, wg, wu, wd
    )
    atol = 2e-4 if dtype == jnp.float32 else 5e-2
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=atol
        )


GG_GRAD_CASES = [  # (E, D, F, group_sizes, row_block)
    (4, 32, 64, (16, 0, 7, 9), 8),  # empty group + ragged tails
    (2, 64, 128, (128, 128), 128),  # exactly tile-aligned
    (3, 96, 160, (1, 50, 13), 16),  # non-power-of-two dims
    (4, 32, 64, (0, 0, 0, 40), 8),  # total imbalance
]


def _grouped_case(rng, E, D, F, gs, bc, dtype):
    from repro.core.dispatch.sorted import aligned_rows

    gs = np.asarray(gs, np.int32)
    N_pad = aligned_rows(int(gs.sum()), E, bc)
    xs = np.full((N_pad, D), 7.5, np.float32)  # poison the padding rows
    padded = (gs + bc - 1) // bc * bc
    starts = np.cumsum(padded) - padded
    for e in range(E):
        xs[starts[e]:starts[e] + gs[e]] = rng.standard_normal((gs[e], D)) * 0.3
    xs = jnp.asarray(xs, dtype)
    wg = jnp.asarray(rng.standard_normal((E, D, F)), dtype) * 0.05
    wu = jnp.asarray(rng.standard_normal((E, D, F)), dtype) * 0.05
    wd = jnp.asarray(rng.standard_normal((E, F, D)), dtype) * 0.05
    return xs, wg, wu, wd, jnp.asarray(gs), N_pad


@pytest.mark.parametrize("case", GG_GRAD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_gemm_grad_parity(rng, case, dtype):
    """jax.grad through the group-size-aware Pallas kernel (dgrad + wgrad +
    SwiGLU recompute) == grad of the masked-loop ref oracle. Covers empty
    experts (whose wgrad must be exactly zero) and poisoned padding rows
    (whose dx must be exactly zero)."""
    from repro.kernels.ops import grouped_gemm
    from repro.kernels.ref import grouped_gemm_ref

    E, D, F, gs, bc = case
    xs, wg, wu, wd, gsj, N_pad = _grouped_case(rng, E, D, F, gs, bc, dtype)
    r = jnp.asarray(rng.standard_normal((N_pad, D)), dtype)

    gk = jax.grad(
        lambda *a: jnp.sum(grouped_gemm(*a, gsj, row_block=bc) * r),
        argnums=(0, 1, 2, 3),
    )(xs, wg, wu, wd)
    gr = jax.grad(
        lambda *a: jnp.sum(grouped_gemm_ref(*a, gsj, row_block=bc) * r),
        argnums=(0, 1, 2, 3),
    )(xs, wg, wu, wd)
    atol = 2e-4 if dtype == jnp.float32 else 5e-2
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=atol
        )
    # empty experts: exactly-zero wgrad (their out blocks are never visited)
    gs_np = np.asarray(gs)
    for e in np.nonzero(gs_np == 0)[0]:
        np.testing.assert_array_equal(np.asarray(gk[1], np.float32)[e], 0.0)


def test_grouped_gemm_grad_matches_xla_path(rng):
    """Kernel-path grads == ragged_dot XLA-path grads on the same routing
    (the two paths Trainer(use_kernel=...) switches between)."""
    from repro.kernels.ops import grouped_gemm, grouped_gemm_xla

    E, D, F, gs, bc = 4, 32, 64, (16, 0, 7, 9), 8
    xs, wg, wu, wd, gsj, N_pad = _grouped_case(rng, E, D, F, gs, bc, jnp.float32)
    # XLA path consumes the compact buffer (row_block=1)
    gs_np = np.asarray(gs)
    padded = (gs_np + bc - 1) // bc * bc
    starts = np.cumsum(padded) - padded
    keep = np.concatenate(
        [np.arange(starts[e], starts[e] + gs_np[e]) for e in range(E)]
    )
    xc = jnp.asarray(np.asarray(xs)[keep])
    r = jnp.asarray(rng.standard_normal((N_pad, D)), jnp.float32)
    rc = jnp.asarray(np.asarray(r)[keep])

    gk = jax.grad(
        lambda *a: jnp.sum(grouped_gemm(*a, gsj, row_block=bc) * r),
        argnums=(1, 2, 3),
    )(xs, wg, wu, wd)
    gx = jax.grad(
        lambda *a: jnp.sum(grouped_gemm_xla(*a, gsj) * rc), argnums=(1, 2, 3)
    )(xc, wg, wu, wd)
    for a, b in zip(gk, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_grouped_gemm_backward_saves_no_nf_residual(rng):
    """The recompute contract: the custom_vjp forward saves only the O(N*D)
    inputs — never an (N, F) gate/up/h intermediate."""
    from repro.kernels.expert_gemm import grouped_gemm_residuals

    E, D, F, gs, bc = 4, 32, 64, (16, 0, 7, 9), 8
    xs, wg, wu, wd, gsj, N_pad = _grouped_case(rng, E, D, F, gs, bc, jnp.float32)
    res = grouped_gemm_residuals(xs, wg, wu, wd, gsj, blocks=(bc, 512, 512))
    shapes = [tuple(r.shape) for r in res]
    assert (N_pad, F) not in shapes, shapes
    # residuals are exactly the inputs
    assert sorted(shapes) == sorted(
        [(N_pad, D), (E, D, F), (E, D, F), (E, F, D), (E,)]
    ), shapes


FA_GRAD_CASES = [  # (B, Sq, Sk, H, KV, d, causal, window, blocks)
    (2, 64, 64, 4, 2, 32, True, None, None),  # GQA causal
    (1, 32, 128, 4, 4, 64, True, None, None),  # right-aligned Sq < Sk
    (2, 128, 128, 8, 2, 32, True, 16, None),  # sliding window
    (1, 64, 64, 2, 2, 16, False, None, None),  # non-causal (encoder)
] + FA_TILED_CASES


@pytest.mark.parametrize("case", FA_GRAD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad_parity(rng, case, dtype):
    """jax.grad through the two-pass flash backward (p recomputed from the
    saved logsumexp) == grad of the dense softmax reference."""
    B, Sq, Sk, H, KV, d, causal, window, blocks = case
    q = jnp.asarray(rng.standard_normal((B, Sq, H, d)), dtype) * 0.3
    k = jnp.asarray(rng.standard_normal((B, Sk, KV, d)), dtype) * 0.3
    v = jnp.asarray(rng.standard_normal((B, Sk, KV, d)), dtype) * 0.3
    r = jnp.asarray(rng.standard_normal((B, Sq, H, d)), dtype)
    G = H // KV

    def loss_k(q, k, v):
        out = flash_attention(q, k, v, causal=causal, window=window, blocks=blocks)
        return jnp.sum(out * r)

    def loss_r(q, k, v):
        kb, vb = jnp.repeat(k, G, 2), jnp.repeat(v, G, 2)
        return jnp.sum(
            flash_attention_ref(q, kb, vb, causal=causal, window=window) * r
        )

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    atol = 5e-4 if dtype == jnp.float32 else 6e-2
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=atol
        )


def test_attention_core_kernel_path_grad(rng):
    """use_kernel=True routes attention_core through the Pallas kernel with
    matching values AND grads vs the XLA path."""
    from repro.models.attention import attention_core

    B, S, H, KV, d = 2, 64, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((B, S, KV, d)), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((B, S, KV, d)), jnp.float32) * 0.3
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    r = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32)

    def loss(q, k, v, uk):
        return jnp.sum(attention_core(q, k, v, pos, pos, use_kernel=uk) * r)

    y0 = attention_core(q, k, v, pos, pos, use_kernel=False)
    y1 = attention_core(q, k, v, pos, pos, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5)
    g0 = jax.grad(lambda *a: loss(*a, False), argnums=(0, 1, 2))(q, k, v)
    g1 = jax.grad(lambda *a: loss(*a, True), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_pick_lane_alignment():
    """_pick never returns a misaligned tile smaller than the dim: for the
    lane dims (align=128) it picks the largest multiple-of-128 divisor; the
    row/sublane dim only needs align=8, so padded capacities like C=192
    stay legal."""
    from repro.kernels.expert_gemm import _pick

    assert _pick(512, 384) == 384
    assert _pick(256, 384) == 128  # old halving loop landed on 96-ish splits
    assert _pick(512, 640) == 128
    assert _pick(512, 1536) == 512
    assert _pick(128, 96) == 96  # non-128-divisible dims: whole-dim tile
    assert _pick(512, 160) == 160
    for block, dim in [(512, 384), (128, 256), (512, 640)]:
        assert _pick(block, dim) % 128 == 0
    # row dim: sublane alignment preferred, never crashes on odd capacities
    assert _pick(128, 192, align=8) == 96
    assert _pick(128, 320, align=8) == 80
    assert _pick(128, 1, align=8) == 1
    assert _pick(128, 282, align=8) == 94  # no 8-divisor: largest divisor


SCHEDULE_CASES = [  # (Sq, Sk, bq, bk, causal, window)
    (512, 512, 128, 128, True, None),  # causal
    (4096, 4096, 512, 512, True, None),  # the train cell's shape
    (256, 256, 64, 64, True, 24),  # window narrower than a tile
    (256, 256, 64, 32, True, 96),  # window wider than a tile
    (128, 512, 32, 128, True, None),  # right-aligned Sq < Sk
    (64, 256, 32, 64, True, 16),  # right-aligned window: dead k-blocks
    (256, 256, 64, 64, False, 40),  # window alone
    (256, 512, 64, 128, False, None),  # non-causal: the full grid
]


@pytest.mark.parametrize("case", SCHEDULE_CASES)
def test_flash_schedule_walks_exactly_the_live_tiles(case):
    """The schedule's computed tiles are the tiles a brute-force scan of the
    dense mask finds any unmasked entry in, unmasked only where every entry
    is; each q-block (k-block) is initialised on its first live tile and
    written on its last, in walk order; a row with no live tile still
    initialises and writes once."""
    from repro.kernels.flash_attention import EDGE, FULL, INIT, WRITE, flash_schedule

    Sq, Sk, bq, bk, causal, window = case
    qpos = np.arange(Sq)[:, None] + Sk - Sq
    kpos = np.arange(Sk)[None, :]
    mask = np.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    nq, nk = Sq // bq, Sk // bk
    tiles = mask.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    live = {(qi, ki) for qi in range(nq) for ki in range(nk) if tiles[qi, ki].any()}
    full = {(qi, ki) for qi in range(nq) for ki in range(nk) if tiles[qi, ki].all()}

    s = flash_schedule(Sq, Sk, bq, bk, causal, window)
    assert (s.nq, s.nk, s.full) == (nq, nk, nq * nk)
    assert s.live == len(live)
    for walk, row in ((s.q_walk, 0), (s.k_walk, 1)):
        pairs = [(qi, ki) for qi, ki, f in walk if f & (FULL | EDGE)]
        assert set(pairs) == live and len(pairs) == len(live)
        assert {(qi, ki) for qi, ki, f in walk if f & FULL} == full
        assert not any(f & FULL and f & EDGE for *_, f in walk)
        keys = [(t[row], t[1 - row]) for t in walk]
        assert keys == sorted(keys)  # q-major, resp. k-major
        for r in range(nq if row == 0 else nk):
            steps = [t for t in walk if t[row] == r]
            assert steps[0][2] & INIT and steps[-1][2] & WRITE
            assert sum(bool(t[2] & INIT) for t in steps) == 1
            assert sum(bool(t[2] & WRITE) for t in steps) == 1
            mine = sorted(p[1 - row] for p in live if p[row] == r)
            if mine:
                assert [t[1 - row] for t in steps] == mine
            else:
                assert len(steps) == 1 and not steps[0][2] & (FULL | EDGE)
    assert s.steps == {"fwd": len(s.q_walk), "dq": len(s.q_walk),
                       "dkv": len(s.k_walk)}
    if not causal and window is None:
        assert s.live == s.full == s.steps["fwd"] == s.steps["dkv"]


def test_flash_schedule_per_head_walk():
    """dk/dv walk each k-block's tiles once per query head of the group,
    initialising on the first head and writing on the last."""
    from repro.kernels.flash_attention import INIT, WRITE, _per_head, flash_schedule

    s = flash_schedule(256, 256, 64, 64, True, None)
    walk = _per_head(s.k_walk, 3)
    assert len(walk) == 3 * len(s.k_walk)
    for ki in range(s.nk):
        steps = [t for t in walk if t[2] == ki]
        assert [t[0] for t in steps] == sorted(t[0] for t in steps)
        assert [bool(t[3] & INIT) for t in steps] == [True] + [False] * (len(steps) - 1)
        assert [bool(t[3] & WRITE) for t in steps] == [False] * (len(steps) - 1) + [True]


def test_flash_tiles_from_shape():
    """Tiles are the largest divisors up to 512, the whole dim for short
    sequences, and shrink until the backward's VMEM working set fits."""
    from repro.kernels.flash_attention import VMEM_BUDGET, flash_schedule, flash_tiles, vmem_bytes

    assert flash_tiles(4096, 4096, 64) == (512, 512)
    assert flash_schedule(4096, 4096, 512, 512, True, None).live == 36
    assert flash_tiles(4096, 4096, 128) == (512, 512)
    assert flash_tiles(64, 64, 32) == (64, 64)
    assert flash_tiles(1, 256, 64) == (1, 256)
    assert flash_tiles(1536, 1536, 64) == (512, 512)
    assert flash_tiles(640, 640, 64) == (320, 320)
    bq, bk = flash_tiles(4096, 4096, 1024)
    assert vmem_bytes(bq, bk, 1024, 2) <= VMEM_BUDGET < vmem_bytes(512, 512, 1024, 2)
    assert 4096 % bq == 0 and 4096 % bk == 0 and bq % 8 == 0 and bk % 8 == 0


def test_flash_matches_model_blockwise_path(rng):
    """Kernel vs the model's blockwise XLA attention (same schedule)."""
    from repro.models.attention import attention_core

    B, S, H, KV, d = 2, 128, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, d)), jnp.float32) * 0.3
    k = jnp.asarray(rng.standard_normal((B, S, KV, d)), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((B, S, KV, d)), jnp.float32) * 0.3
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    y_model = attention_core(q, k, v, pos, pos)
    y_kernel = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kernel), atol=1e-5)
