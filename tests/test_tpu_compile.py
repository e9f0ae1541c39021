"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see the TPU's tiling rules,
VMEM limits or Mosaic's lowering restrictions; the TPU compiler can, and it
compiles for a chip that is described but not attached. Each test lowers
one kernel program at the ``llama3-e8t2`` published widths (D 4096,
F 14336, E 8, 32 query / 8 KV heads of 128, 16-token pages) — and the
upcycled ``stablelm-1.6b`` attention widths (32 heads of 64) — and asserts
the compiled HLO carries the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library at once, and under pytest-xdist
every worker imports this file. The persistent compilation cache is off
around these compiles (an entry written for a described chip cannot be
read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import expert_gemm as eg
from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa
from repro.core.dispatch.sorted import aligned_rows

D, F, E, K = 4096, 14336, 8, 2
H, KV, HD = 32, 8, 128
PS = 16
BC = eg.DEFAULT_BLOCKS[0]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler plug-in in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True),
                                               ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """The kernel wrappers compile for the TPU, interpret on the CPU, and
    refuse any other backend rather than interpret there."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("heads", [(H, KV, HD, 2048), (32, 32, 64, 2048),
                                   (32, 32, 64, 4096)],
                         ids=["e8t2", "stablelm", "stablelm-s4096"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles(one_chip, heads, direction):
    """Shape-derived tiles and the live-tile schedule compile; S 4096 with
    32 heads of 64 is the train cell's shape, tiles (512, 512)."""
    h, kv, d, S = heads

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, interpret=False)

    def bwd(q, k, v):
        return jax.grad(
            lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    _compile(fwd if direction == "fwd" else bwd, one_chip,
             ((1, S, h, d), jnp.bfloat16), ((1, S, kv, d), jnp.bfloat16),
             ((1, S, kv, d), jnp.bfloat16))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "q8"])
def test_paged_attention_compiles(one_chip, quant):
    B, maxP = 4, 10
    pages = B * maxP
    pool = (pages, KV, PS, HD)
    tables = [((B, H, HD), jnp.bfloat16)]
    if quant:
        tables += [(pool, jnp.int8), (pool, jnp.int8),
                   ((pages, KV, PS, 1), jnp.float32),
                   ((pages, KV, PS, 1), jnp.float32)]
        fn = lambda q, k, v, ks, vs, bt, sl: pa.paged_attention_q8(  # noqa: E731
            q, k, v, ks, vs, bt, sl, interpret=False)
    else:
        tables += [(pool, jnp.bfloat16), (pool, jnp.bfloat16)]
        fn = lambda q, k, v, bt, sl: pa.paged_attention(  # noqa: E731
            q, k, v, bt, sl, interpret=False)
    tables += [((B, maxP), jnp.int32), ((B,), jnp.int32)]
    _compile(fn, one_chip, *tables)


def _ffn_weights(dtype):
    return [((E, D, F), dtype), ((E, D, F), dtype), ((E, F, D), dtype)]


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_grouped_gemm_compiles(one_chip, direction):
    N_pad = aligned_rows(2048 * K, E, BC)

    def fwd(xs, wg, wu, wd, gs):
        return eg.grouped_gemm(xs, wg, wu, wd, gs, interpret=False)

    def bwd(xs, wg, wu, wd, gs):
        return jax.grad(
            lambda *a: fwd(*a, gs).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3)
        )(xs, wg, wu, wd)

    _compile(fwd if direction == "fwd" else bwd, one_chip,
             ((N_pad, D), jnp.bfloat16), *_ffn_weights(jnp.bfloat16),
             ((E,), jnp.int32))


def test_grouped_gemm_q8_compiles(one_chip):
    N_pad = aligned_rows(2048 * K, E, BC)
    _compile(
        lambda xs, wg, wu, wd, sg, su, sd, gs: eg.grouped_gemm_q8(
            xs, wg, wu, wd, sg, su, sd, gs, interpret=False),
        one_chip, ((N_pad, D), jnp.bfloat16), *_ffn_weights(jnp.int8),
        ((E, F), jnp.bfloat16), ((E, F), jnp.bfloat16), ((E, D), jnp.bfloat16),
        ((E,), jnp.int32),
    )


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "q8"])
def test_grouped_gemm_fused_compiles(one_chip, quant):
    T = 256
    N = T * K
    idx = [((E,), jnp.int32), ((N,), jnp.int32), ((N,), jnp.int32),
           ((N,), jnp.int32), ((N,), jnp.float32)]
    if quant:
        _compile(
            lambda x, wg, wu, wd, sg, su, sd, gs, tok, dst, sl, g:
            eg.grouped_gemm_fused_q8(x, wg, wu, wd, sg, su, sd, gs, tok, dst,
                                     sl, g, interpret=False),
            one_chip, ((T, D), jnp.bfloat16), *_ffn_weights(jnp.int8),
            ((E, F), jnp.bfloat16), ((E, F), jnp.bfloat16),
            ((E, D), jnp.bfloat16), *idx,
        )
    else:
        _compile(
            lambda x, wg, wu, wd, gs, tok, dst, sl, g: eg.grouped_gemm_fused(
                x, wg, wu, wd, gs, tok, dst, sl, g, interpret=False),
            one_chip, ((T, D), jnp.bfloat16), *_ffn_weights(jnp.bfloat16), *idx,
        )
