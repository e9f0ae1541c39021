"""Engine-parity suite: greedy decode through the paged-KV subsystem is
token-for-token identical to the ring-buffer engine (the parity oracle)
across a config sweep — MoE (e8t2), dense (llama3-8b), sliding-window,
sorted dispatcher, Pallas kernels on/off — including mid-stream slot
refill, preemption under a tight page pool, and mid-stream defrag.

Also pins the ring engine's bucketed-prefill compile cache (satellite:
one trace per padded prompt-length bucket, not per request)."""
import dataclasses

import numpy as np
import pytest

from conftest import init_model, tiny_dense
from repro.config import get_config, smoke_config
from repro.serving.engine import Request, ServingEngine


def _dropless(cfg):
    """Finite-CF drop sets depend on dispatch-group token counts, which
    legitimately differ between full prefill and chunked prefill — parity
    checks run dropless, like the prefill==forward equivalence tests.

    Also pins the e8t2 default 'alltoall' to 'allgather': this suite is
    single-host (no EP plan), where alltoall would trip the strict-dispatch
    gate (REPRO_STRICT_DISPATCH=1 in tests/CI) instead of quietly falling
    back — 'allgather' is exactly what the fallback resolved to."""
    if cfg.moe is None:
        return cfg
    moe = dataclasses.replace(cfg.moe, capacity_factor=None)
    if moe.dispatcher == "alltoall":
        moe = dataclasses.replace(moe, dispatcher="allgather")
    return cfg.replace(moe=moe)


def _requests(cfg, seed, n=6, lmin=3, lmax=40, new=(3, 8)):
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, int(rng.integers(lmin, lmax))).astype(np.int32),
            max_new_tokens=int(rng.integers(*new)),
        )
        for i in range(n)
    ]


def _parity(cfg, params, paged_kw, seed=11, n=6, max_batch=3, max_seq=64,
            ring_kw=None, new=(3, 8)):
    ring = ServingEngine(cfg, params, max_batch=max_batch, max_seq=max_seq,
                         **(ring_kw or {}))
    out_ring = ring.run(_requests(cfg, seed, n, new=new))
    paged = ServingEngine(cfg, params, max_batch=max_batch, max_seq=max_seq,
                          cache_mode="paged", **paged_kw)
    out_paged = paged.run(_requests(cfg, seed, n, new=new))
    assert out_ring == out_paged, {
        rid: (out_ring[rid], out_paged[rid])
        for rid in out_ring if out_ring[rid] != out_paged[rid]
    }
    # the pool drains completely: freed == allocated
    paged.page_pool.check_invariants()
    assert paged.page_pool.free_pages == paged.page_pool.num_pages
    return paged


def test_e8t2_one_device_default_dispatcher_fails_loudly(monkeypatch):
    """llama3-e8t2 defaults to the EP 'alltoall' dispatcher. A one-device
    engine has no EP mesh, so under strict dispatch its first step must
    raise instead of quietly running allgather; naming 'sorted' is the
    one-chip path. (smoke_config swaps alltoall for allgather, so the
    published default is put back.)"""
    monkeypatch.setenv("REPRO_STRICT_DISPATCH", "1")
    default = get_config("llama3-e8t2").moe.dispatcher
    assert default == "alltoall"
    cfg = smoke_config(get_config("llama3-e8t2"))
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, dispatcher=default))
    params = init_model(cfg)
    req = lambda: Request(rid=0, prompt=np.arange(5, dtype=np.int32),  # noqa: E731
                          max_new_tokens=2)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32, cache_mode="paged")
    eng.submit(req())
    with pytest.raises(ValueError, match="'alltoall' is illegal"):
        eng.step()
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32, cache_mode="paged",
                        dispatcher="sorted")
    assert len(eng.run([req()])[0]) == 2


def test_serve_launcher_layers_cuts_depth_only(monkeypatch):
    """--layers overrides num_layers and leaves every width alone."""
    from repro.launch import serve

    seen = []

    class Spy(ServingEngine):
        def __init__(self, cfg, *a, **kw):
            seen.append(cfg)
            super().__init__(cfg, *a, **kw)

    from repro.launch import train

    monkeypatch.setattr(serve, "ServingEngine", Spy)
    # the launchers' persistent compile cache would stay on for every later
    # test in this worker; tests/test_compile_cache.py covers it
    for launcher in (serve, train):
        monkeypatch.setattr(launcher, "enable_compile_cache", lambda: None)
    out = serve.main(["--arch", "llama3-e8t2", "--smoke", "--layers", "1",
                      "--dispatcher", "sorted", "--cache-mode", "paged",
                      "--requests", "1", "--max-new", "2", "--prompt-len", "4"])
    assert [len(v) for v in out.values()] == [2]
    smoke = smoke_config(get_config("llama3-e8t2"))
    assert seen[0].num_layers == 1
    assert seen[0].replace(num_layers=smoke.num_layers).param_counts() == \
        smoke.param_counts()
    # at published widths without an EP mesh, the EP default is refused
    # before any weight is allocated
    with pytest.raises(SystemExit):
        serve.main(["--arch", "llama3-e8t2", "--layers", "2"])
    with pytest.raises(SystemExit, match="--dispatcher sorted"):
        train.main(["--arch", "llama3-e8t2", "--layers", "1"])


SWEEP = {
    "llama3-e8t2": {},
    "llama3-8b": {},
    "llama3-e8t2-sorted": dict(dispatcher="sorted"),
}


@pytest.mark.parametrize("arch_tag", sorted(SWEEP))
def test_engine_parity_archs(arch_tag):
    """Paged == ring, token for token, with mid-stream slot refill (6
    requests through 3 slots)."""
    arch = arch_tag.replace("-sorted", "")
    cfg = _dropless(smoke_config(get_config(arch)).replace(dtype="float32"))
    params = init_model(cfg, fp32=True)
    kw = dict(SWEEP[arch_tag])
    _parity(cfg, params, dict(page_size=8, prefill_chunk=16, **kw),
            ring_kw=kw, n=6)


def test_engine_parity_sliding_window():
    """Window config: ring keeps a W-slot ring; paged releases pages below
    the window. Same masked KV set => same tokens."""
    cfg = tiny_dense().replace(dtype="float32", sliding_window=16)
    params = init_model(cfg, fp32=True)
    paged = _parity(cfg, params, dict(page_size=4, prefill_chunk=8), n=5)
    # the window bound held: live pages never exceeded
    # ceil((W + ps)/ps) + 1 per active request
    per_req = paged.page_pool.pages_for(16 + 4) + 1
    assert paged.peak_used_pages <= 3 * per_req


def test_engine_parity_use_kernel():
    """Pallas path on both ends: expert GEMMs + paged-attention decode
    kernel vs the XLA gather path give the same greedy tokens."""
    cfg = _dropless(smoke_config(get_config("llama3-e8t2")).replace(dtype="float32"))
    params = init_model(cfg, fp32=True)
    xla = ServingEngine(cfg, params, max_batch=2, max_seq=48, cache_mode="paged",
                        page_size=8, prefill_chunk=16)
    out_xla = xla.run(_requests(cfg, 7, n=3, lmax=24, new=(3, 6)))
    kern = ServingEngine(cfg, params, max_batch=2, max_seq=48, cache_mode="paged",
                         page_size=8, prefill_chunk=16, use_kernel=True)
    out_kern = kern.run(_requests(cfg, 7, n=3, lmax=24, new=(3, 6)))
    assert out_xla == out_kern


def test_engine_parity_under_preemption():
    """A pool far smaller than ring capacity forces preemption-by-recompute;
    greedy determinism makes the recomputed streams identical."""
    cfg = tiny_dense().replace(dtype="float32")
    params = init_model(cfg, fp32=True)
    paged = _parity(cfg, params, dict(page_size=8, num_pages=8, prefill_chunk=16),
                    seed=0, n=7, new=(6, 12))
    assert sum(r.preemptions for r in paged.sched.requests.values()) > 0, (
        "pool was large enough that preemption never fired — shrink it"
    )


def test_engine_parity_mid_stream_defrag():
    """Defrag (pool compaction + block-table rewrite) mid-stream is
    invisible to the decoded tokens."""
    cfg = tiny_dense().replace(dtype="float32")
    params = init_model(cfg, fp32=True)
    ring = ServingEngine(cfg, params, max_batch=3, max_seq=64)
    out_ring = ring.run(_requests(cfg, 13))

    paged = ServingEngine(cfg, params, max_batch=3, max_seq=64,
                          cache_mode="paged", page_size=4, prefill_chunk=8)
    reqs = _requests(cfg, 13)
    for r in reqs:
        paged.submit(r)
    for i in range(40):
        if not paged.sched.has_work:
            break
        paged.step()
        if i % 3 == 2:
            paged.defrag()
            paged.page_pool.check_invariants()
    assert not paged.sched.has_work
    assert out_ring == {r.rid: r.output for r in reqs}


def test_ring_prefill_compiles_once_per_bucket():
    """Regression (satellite): `_prefill_into_slot` used to build a fresh
    jax.jit per call, retracing every prefill. Prompts of length 5/6/7
    share the 16-bucket, 17 lands in 32 => exactly two traces."""
    cfg = tiny_dense().replace(dtype="float32")
    params = init_model(cfg, fp32=True)
    engine = ServingEngine(cfg, params, max_batch=4, max_seq=64)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                max_new_tokens=2)
        for i, L in enumerate([5, 6, 7, 17])
    ]
    engine.run(reqs)
    assert engine.prefill_traces == 2, engine.prefill_traces
    # same buckets again: zero new traces even across fresh requests
    more = [
        Request(rid=10 + i, prompt=rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                max_new_tokens=2)
        for i, L in enumerate([4, 9, 20])
    ]
    engine.run(more)
    assert engine.prefill_traces == 2, engine.prefill_traces


# -- prefix-cache KV reuse ---------------------------------------------------


def _shared_prefix_requests(cfg, seed, n=6, stem_len=12, tail=(1, 10),
                            new=(3, 6)):
    """n requests sharing one stem: rid 4 is the bare stem and admits in
    the second wave once the stem is cached (full-coverage hit -> COW);
    the rest append random tails (partial hits)."""
    rng = np.random.default_rng(seed)
    stem = rng.integers(0, cfg.vocab_size, stem_len).astype(np.int32)
    reqs = []
    for i in range(n):
        t = rng.integers(0, cfg.vocab_size, int(rng.integers(*tail))).astype(np.int32)
        prompt = stem.copy() if i == 4 else np.concatenate([stem, t])
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(*new))))
    return reqs


def test_prefix_cache_parity_and_hit_accounting():
    """Shared-prefix traffic with the radix cache on decodes token-for-token
    what the cache-less paged engine decodes, while the stats show real
    hits, credited admission, and at least one COW clone."""
    cfg = tiny_dense().replace(dtype="float32")
    params = init_model(cfg, fp32=True)
    kw = dict(max_batch=3, max_seq=64, cache_mode="paged", page_size=4,
              prefill_chunk=8)
    plain = ServingEngine(cfg, params, **kw)
    out_plain = plain.run(_shared_prefix_requests(cfg, 5))
    cached = ServingEngine(cfg, params, prefix_cache=True, **kw)
    out_cached = cached.run(_shared_prefix_requests(cfg, 5))
    assert out_plain == out_cached
    st = cached.kv_stats()["prefix"]
    # 6 requests x 12-token stem through 3 slots: the later waves must hit
    assert st["hits"] > 0 and st["hit_pages"] > 0
    assert st["hit_tokens"] > 0 and st["inserted_pages"] >= 3
    assert st["cow_clones"] >= 1, "the bare-stem request never COW-cloned"
    # drained: every cached page at refcount zero, fully reclaimable
    pool = cached.page_pool
    pool.check_invariants()
    assert not pool._refs and all(r == 0 for r in pool._shared.values())
    pool.drop_prefix_cache()
    assert pool.free_pages == pool.num_pages


def test_cow_never_mutates_shared_pages_on_device():
    """Device-content check: a full-coverage hit writes its recompute chunk
    and decode tokens into the COW clone and fresh pages — the cached KV
    pages are bit-identical before and after the hit request's lifetime."""
    import jax

    cfg = tiny_dense().replace(dtype="float32")
    params = init_model(cfg, fp32=True)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=64,
                        cache_mode="paged", page_size=4, prefill_chunk=8,
                        prefix_cache=True)
    stem = np.arange(12, dtype=np.int32) % cfg.vocab_size
    out0 = eng.run([Request(rid=0, prompt=stem.copy(), max_new_tokens=3)])
    pool = eng.page_pool
    shared = sorted(pool._shared)
    assert len(shared) == 3, "12-token prompt should cache 3 full pages"
    before = [np.asarray(leaf[:, shared])
              for leaf in jax.tree.leaves(eng.pool_dev)]
    out1 = eng.run([Request(rid=1, prompt=stem.copy(), max_new_tokens=5)])
    assert pool.cow_clones >= 1
    assert out1[1][:3] == out0[0], "same prompt, same greedy stream"
    after = [np.asarray(leaf[:, shared])
             for leaf in jax.tree.leaves(eng.pool_dev)]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)


# -- speculative decoding ----------------------------------------------------


def test_speculative_parity_same_drafter():
    """Drafter == verifier (same params): token-for-token parity with the
    plain paged engine, every draft accepted, and verify steps strictly
    fewer than one-token-per-step decode would need."""
    from repro.serving.speculative import SpeculativeEngine

    cfg = tiny_dense().replace(dtype="float32")
    params = init_model(cfg, fp32=True)
    kw = dict(max_batch=2, max_seq=64, page_size=4, prefill_chunk=8)
    plain = ServingEngine(cfg, params, cache_mode="paged", **kw)
    out_plain = plain.run(_requests(cfg, 21, n=4, lmax=20, new=(4, 9)))
    spec = SpeculativeEngine(cfg, params, cfg, params, draft_k=3, **kw)
    out_spec = spec.run(_requests(cfg, 21, n=4, lmax=20, new=(4, 9)))
    assert out_plain == out_spec
    assert spec.drafted_tokens > 0 and spec.acceptance_rate == 1.0
    total_new = sum(len(o) for o in out_spec.values())
    assert spec.spec_steps < total_new, "speculation never batched tokens"
    spec.page_pool.check_invariants()
    assert spec.page_pool.free_pages == spec.page_pool.num_pages


def test_speculative_parity_with_bad_drafter():
    """A drafter that disagrees with the verifier (independently initialized
    params) costs acceptance, never correctness: greedy output is still
    identical to non-speculative decode."""
    from repro.serving.speculative import SpeculativeEngine

    cfg = tiny_dense().replace(dtype="float32")
    params = init_model(cfg, fp32=True)
    bad = init_model(cfg, seed=99, fp32=True)
    kw = dict(max_batch=2, max_seq=64, page_size=4, prefill_chunk=8)
    plain = ServingEngine(cfg, params, cache_mode="paged", **kw)
    out_plain = plain.run(_requests(cfg, 23, n=4, lmax=20, new=(4, 9)))
    spec = SpeculativeEngine(cfg, params, cfg, bad, draft_k=3, **kw)
    out_spec = spec.run(_requests(cfg, 23, n=4, lmax=20, new=(4, 9)))
    assert out_plain == out_spec
    assert spec.acceptance_rate < 1.0, (
        "independent random params should disagree somewhere"
    )


def test_speculative_from_upcycle_pair():
    """The paper's pairing: upcycle the dense parent into the MoE, draft on
    dense, verify on MoE. Function-preserving init (Mixtral router) makes
    acceptance ~1; output matches a plain engine serving the same MoE."""
    from repro.config import MoEConfig
    from repro.core.upcycle import upcycle_config, upcycle_params
    from repro.serving.speculative import SpeculativeEngine

    dense = tiny_dense().replace(dtype="float32")
    dp = init_model(dense, fp32=True)
    moe_cfg = _dropless(upcycle_config(
        dense, MoEConfig(num_experts=4, top_k=2, capacity_factor=None)
    ))
    kw = dict(max_batch=2, max_seq=64, page_size=4, prefill_chunk=8)
    spec = SpeculativeEngine.from_upcycle(dense, moe_cfg, dp, draft_k=3, **kw)
    assert spec.provenance is not None
    out_spec = spec.run(_requests(moe_cfg, 29, n=4, lmax=20, new=(4, 9)))
    import jax

    mp = upcycle_params(dense, moe_cfg, dp, jax.random.PRNGKey(0))
    plain = ServingEngine(moe_cfg, mp, cache_mode="paged", **kw)
    out_plain = plain.run(_requests(moe_cfg, 29, n=4, lmax=20, new=(4, 9)))
    assert out_spec == out_plain
    assert spec.acceptance_rate > 0.9, spec.kv_stats()["speculation"]


def test_speculative_with_prefix_cache():
    """The two features compound: prefix hits skip prefill for drafter AND
    verifier (lockstep pools), speculation still decodes the exact greedy
    stream."""
    from repro.serving.speculative import SpeculativeEngine

    cfg = tiny_dense().replace(dtype="float32")
    params = init_model(cfg, fp32=True)
    kw = dict(max_batch=2, max_seq=64, page_size=4, prefill_chunk=8)
    plain = ServingEngine(cfg, params, cache_mode="paged", **kw)
    out_plain = plain.run(_shared_prefix_requests(cfg, 31, n=5))
    spec = SpeculativeEngine(cfg, params, cfg, params, draft_k=3,
                             prefix_cache=True, **kw)
    out_spec = spec.run(_shared_prefix_requests(cfg, 31, n=5))
    assert out_plain == out_spec
    stats = spec.kv_stats()
    assert stats["prefix"]["hits"] > 0
    assert stats["speculation"]["acceptance_rate"] == 1.0


def test_bucketed_prefill_matches_exact():
    """Right-padded bucketed prefill (valid_len path) produces the same
    tokens as an engine whose bucket is the exact prompt length."""
    cfg = tiny_dense().replace(dtype="float32")
    params = init_model(cfg, fp32=True)
    bucketed = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    out_b = bucketed.run(_requests(cfg, 17, n=4, lmax=30))
    exact = ServingEngine(cfg, params, max_batch=2, max_seq=64)
    exact._bucket = lambda L: L  # defeat bucketing
    out_e = exact.run(_requests(cfg, 17, n=4, lmax=30))
    assert out_b == out_e
    assert bucketed.prefill_traces < exact.prefill_traces
