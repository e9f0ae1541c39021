"""Serving bench: paged-KV vs ring-buffer engine on the e8t2 smoke config.

Runs the same mixed-length greedy workload through both cache backends and
emits machine-readable ``BENCH_serving.json`` at the repo root (plus the
usual CSV/JSON via benchmarks.common) with, per engine:

* ``tokens_per_s``        — end-to-end decode throughput (CPU wall time;
                            not hardware-representative, tracked for trend)
* ``p50_ms`` / ``p99_ms`` — per-token latency percentiles (each emitted
                            token is attributed its engine step's wall time)
* ``kv_bytes_resident``   — peak KV bytes actually pinned: the ring cache
                            pins ``max_batch * max_seq`` entries up front;
                            the paged cache pins only allocated pages
* ``page_utilization``    — peak allocated / pool size (paged only)
* ``prefill_traces``      — compiled prefill variants (ring: one per
                            length bucket; paged: 1 chunk + 1 decode step)

Asserted here (the acceptance gate): paged resident KV <= ring resident KV
at equal batch, and greedy outputs token-for-token identical across
engines.

**Prefix-reuse section** (``"prefix_reuse"``): cached vs cache-less paged
engine at 0% / 50% / 90% shared-prefix traffic — live-peak KV bytes
(shared pages counted once), tokens/s, hit tokens, COW clones; asserts
parity at every fraction and a strict live-bytes reduction at >= 50%.

**Speculation section** (``"speculation"``): the dense parent drafts
DRAFT_K tokens, the upcycled MoE verifies in one step — acceptance rate,
tokens/s vs the non-speculative baseline; asserts token parity and > 0.9
acceptance (function-preserving upcycling).

**Quantized-KV section** (``"quant"``): int8 KV pages (per-token scale
sidecar) vs bf16 pages at a FIXED pool byte budget, on a briefly-trained
greedy-parity probe model — page counts, peak concurrent resident
requests; asserts >= 1.5x residency for int8 and exact token parity.

**Multi-device scaling section** (``"scaling"`` in the JSON): subprocess
workers rerun a pool-bound paged workload on 1 / 2 / 4 fake CPU devices
(``--xla_force_host_platform_device_count`` — device count locks at first
jax init, hence subprocesses) through the mesh-aware engine, scaling the
DP shard count with the device count plus one EP x DP topology (dp=2,
ep=2) for the overlapped expert all-to-all. Per row: tokens/s, aggregate
and per-device peak resident KV bytes, and the scheduler's peak
concurrent-resident-request count. Asserted: >= 1.8x resident requests at
2 devices vs 1, and EP decode parity (every topology emits exactly the
single-device token streams).
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.config import get_config, smoke_config
from repro.models.model import model_decl
from repro.serving.engine import Request, ServingEngine
from repro.sharding.rules import init_from_decls

ROOT_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serving.json",
)

MAX_BATCH, MAX_SEQ = 4, 96
N_REQ, MAX_NEW = 8, 12
PAGE_SIZE, PREFILL_CHUNK = 8, 16


def make_requests(cfg, seed=0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(6, 48, N_REQ)
    return [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, int(L)).astype(np.int32),
                max_new_tokens=MAX_NEW)
        for i, L in enumerate(lens)
    ]


def drive(engine, requests):
    """Run to drain, attributing each emitted token its step wall time."""
    for r in requests:
        engine.submit(r)
    per_token_ms = []
    t0 = time.perf_counter()
    while True:
        if engine.cache_mode == "paged":
            if not engine.sched.has_work:
                break
        elif not (any(engine.slots) or engine.queue):
            break
        before = sum(len(r.output) for r in requests)
        ts = time.perf_counter()
        engine.step()
        dt_ms = (time.perf_counter() - ts) * 1e3
        emitted = sum(len(r.output) for r in requests) - before
        per_token_ms.extend([dt_ms / max(emitted, 1)] * emitted)
    wall = time.perf_counter() - t0
    total = sum(len(r.output) for r in requests)
    lat = np.asarray(per_token_ms) if per_token_ms else np.zeros(1)
    kv = engine.kv_stats()
    return {
        "tokens": total,
        "tokens_per_s": round(total / wall, 2),
        "p50_ms": round(float(np.percentile(lat, 50)), 2),
        "p99_ms": round(float(np.percentile(lat, 99)), 2),
        "kv_bytes_resident": int(kv["kv_bytes_peak"]),
        "page_utilization": round(
            kv["peak_used_pages"] / max(kv["num_pages"], 1), 3
        )
        if engine.cache_mode == "paged"
        else 1.0,
        "peak_used_pages": int(kv["peak_used_pages"]),
        "num_pages": int(kv["num_pages"]),
        "prefill_traces": getattr(engine, "prefill_traces", 0),
    }, {r.rid: list(r.output) for r in requests}


# -- graceful degradation under overload -------------------------------------
def run_resilience(cfg, params):
    """Overload a deliberately tiny shed-configured engine (the
    ``resilience`` report section): admission control must shed loudly
    (typed ShedError, request never enqueued), deadlines must evict on
    time, and the drained engine must end with zero resident pages. The
    counters come from :meth:`ServingEngine.health` — the same snapshot an
    external load-balancer polls."""
    from repro.resilience import ShedError

    engine = ServingEngine(
        cfg, params, max_batch=2, max_seq=MAX_SEQ, cache_mode="paged",
        page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK, num_pages=16,
        max_queue=2, shed_watermark=1, deadline_steps=40,
    )
    accepted, shed = [], 0
    for r in make_requests(cfg, seed=3):
        try:
            engine.submit(r)
            accepted.append(r)
        except ShedError:
            shed += 1
    steps = 0
    while engine.sched.has_work and steps < 2000:
        engine.step()
        steps += 1
    h = engine.health()
    assert h["shed_count"] == shed
    assert h["resident_pages"] == 0, "page leak after drain"
    assert shed + len(accepted) == N_REQ
    return {
        "workload": {"requests": N_REQ, "max_batch": 2, "num_pages": 16,
                     "max_queue": 2, "shed_watermark": 1, "deadline_steps": 40},
        "accepted": len(accepted),
        "shed_count": int(h["shed_count"]),
        "deadline_evictions": int(h["deadline_evictions"]),
        "completed_ok": sum(
            1 for r in accepted
            if r.status == "ok" and len(r.output) >= r.max_new_tokens
        ),
        "resident_pages_after_drain": int(h["resident_pages"]),
    }


# -- prefix-cache KV reuse ----------------------------------------------------
PREFIX_LEN, PREFIX_FRACS = 48, (0.0, 0.5, 0.9)  # 6 shared pages at ps=8


def _prefix_stem(cfg):
    return np.random.default_rng(4).integers(
        0, cfg.vocab_size, PREFIX_LEN
    ).astype(np.int32)


def _prefix_requests(cfg, frac, seed=5):
    """N_REQ requests; a ``frac`` fraction share a PREFIX_LEN-token stem
    (system-prompt traffic), spread evenly through the stream so every
    admission wave carries the same share — the live-KV peak then reflects
    concurrent sharing, not which wave happened to be all-random."""
    rng = np.random.default_rng(seed)
    stem = _prefix_stem(cfg)
    n_share = int(round(frac * N_REQ))
    share_ids = ({int(round(j * N_REQ / n_share)) for j in range(n_share)}
                 if n_share else set())
    reqs = []
    for i in range(N_REQ):
        tail = rng.integers(0, cfg.vocab_size, int(rng.integers(4, 16))).astype(np.int32)
        prompt = (np.concatenate([stem, tail]) if i in share_ids
                  else np.concatenate([rng.integers(0, cfg.vocab_size, PREFIX_LEN).astype(np.int32), tail]))
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=MAX_NEW))
    return reqs


def run_prefix_reuse(cfg, params):
    """Cached vs cache-less paged engine at 0% / 50% / 90% shared-prefix
    traffic. The headline metric is ``kv_bytes_live_peak`` — pages
    *referenced by live requests*, shared pages counted once (refcount-0
    cache residue is reclaimable on demand, like OS page cache, so it is
    excluded). Both engines first serve one bare-stem priming request
    (real prefix traffic finds the system prompt already warm; the
    cache-less engine pays the same priming work), then the measured
    workload. Asserted: token-for-token parity at every fraction, and a
    strict live-bytes reduction once >= 50% of traffic shares the stem."""
    rows = []
    for frac in PREFIX_FRACS:
        row = {"name": f"shared_{int(frac * 100)}pct", "shared_frac": frac}
        outs = {}
        for tag, cache in (("uncached", False), ("cached", True)):
            engine = ServingEngine(
                cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                cache_mode="paged", page_size=PAGE_SIZE,
                prefill_chunk=PREFILL_CHUNK, prefix_cache=cache,
            )
            engine.run([Request(rid=10_000, prompt=_prefix_stem(cfg),
                                max_new_tokens=1)])
            stats, outs[tag] = drive(engine, _prefix_requests(cfg, frac))
            kv = engine.kv_stats()
            row[tag] = {
                "tokens_per_s": stats["tokens_per_s"],
                "kv_bytes_live_peak": int(kv["kv_bytes_live_peak"]),
                "peak_live_pages": int(kv["peak_live_pages"]),
            }
            if cache:
                row["hit_tokens"] = int(kv["prefix"]["hit_tokens"])
                row["cow_clones"] = int(kv["prefix"]["cow_clones"])
                engine.page_pool.drop_prefix_cache()
                engine.page_pool.check_invariants()
                assert engine.page_pool.free_pages == engine.page_pool.num_pages
        row["parity"] = outs["cached"] == outs["uncached"]
        assert row["parity"], f"prefix cache changed tokens at frac={frac}"
        row["live_bytes_saved"] = (row["uncached"]["kv_bytes_live_peak"]
                                   - row["cached"]["kv_bytes_live_peak"])
        rows.append(row)
        print(f"  prefix {row['name']}: live peak "
              f"{row['cached']['kv_bytes_live_peak']/1e6:.2f} MB cached vs "
              f"{row['uncached']['kv_bytes_live_peak']/1e6:.2f} MB uncached, "
              f"{row.get('hit_tokens', 0)} hit tokens")
    for row in rows:
        if row["shared_frac"] >= 0.5:
            assert row["live_bytes_saved"] > 0, (
                f"prefix sharing saved no live KV at {row['name']}: {row}"
            )
    return {
        "workload": {
            "requests": N_REQ, "max_new": MAX_NEW, "max_batch": MAX_BATCH,
            "prefix_len": PREFIX_LEN, "page_size": PAGE_SIZE,
            "prefill_chunk": PREFILL_CHUNK,
        },
        "rows": rows,
    }


# -- speculative decoding -----------------------------------------------------
DRAFT_K = 4


def run_speculation(cfg):
    """Dense-parent speculative decoding on the paper's pairing: upcycle
    the dense parent into the served MoE (function-preserving), draft
    DRAFT_K tokens on the parent, verify in one MoE step. Asserted:
    token-for-token parity with non-speculative decode and near-total
    acceptance (the whole point of serving an upcycled checkpoint with its
    parent as drafter)."""
    from repro.core.upcycle import upcycle_params
    from repro.serving.speculative import SpeculativeEngine

    dense_cfg = cfg.replace(name=f"{cfg.name}-parent", family="dense", moe=None)
    dense_params = init_from_decls(model_decl(dense_cfg), jax.random.PRNGKey(0))
    kw = dict(max_batch=MAX_BATCH, max_seq=MAX_SEQ, page_size=PAGE_SIZE,
              prefill_chunk=PREFILL_CHUNK)
    spec = SpeculativeEngine.from_upcycle(dense_cfg, cfg, dense_params,
                                          draft_k=DRAFT_K, **kw)
    spec_stats, spec_outs = drive(spec, make_requests(cfg, seed=9))
    moe_params = upcycle_params(dense_cfg, cfg, dense_params,
                                jax.random.PRNGKey(0))
    base = ServingEngine(cfg, moe_params, cache_mode="paged", **kw)
    base_stats, base_outs = drive(base, make_requests(cfg, seed=9))
    assert spec_outs == base_outs, "speculative decode changed greedy tokens"
    s = spec.kv_stats()["speculation"]
    assert s["acceptance_rate"] > 0.9, s
    spec.page_pool.check_invariants()
    assert spec.page_pool.free_pages == spec.page_pool.num_pages
    print(f"  speculation: k={DRAFT_K}, acceptance {s['acceptance_rate']:.2%}, "
          f"{spec_stats['tokens_per_s']} tok/s speculative vs "
          f"{base_stats['tokens_per_s']} baseline")
    return {
        "workload": {"requests": N_REQ, "max_new": MAX_NEW,
                     "max_batch": MAX_BATCH, "page_size": PAGE_SIZE},
        "draft_k": DRAFT_K,
        "acceptance_rate": s["acceptance_rate"],
        "drafted_tokens": s["drafted_tokens"],
        "accepted_tokens": s["accepted_tokens"],
        "verify_steps": s["spec_steps"],
        "tokens_per_s_speculative": spec_stats["tokens_per_s"],
        "tokens_per_s_baseline": base_stats["tokens_per_s"],
        "parity_token_for_token": spec_outs == base_outs,
    }


# -- quantized KV pages at a fixed pool byte budget ---------------------------
# bf16 engine gets QUANT_PAGES_BF16 pages; the int8 engine gets however many
# pages fit the SAME byte budget (int8 payload + f32 scale sidecar per
# token-head vs 2 bytes/elem -> ~1.9x pages at head_dim 64+). Each request
# pins up to 5 pages (24-token prompt + 8 new at page_size 8, same
# accounting as the scaling section). QUANT_REQS and max_batch sit well
# above either pool's concurrent capacity so free pages — not the workload
# — bound peak residency on both sides.
QUANT_PAGES_BF16, QUANT_PROMPT, QUANT_NEW, QUANT_REQS = 20, 24, 8, 16


def run_quant_kv():
    """int8 KV pages vs bf16 pages at a FIXED page-pool byte budget (the
    ``quant`` report section). Params are first sharpened into a greedy-
    parity probe (see quant.sharpen_for_parity: random-init logits are
    near-uniform, so token parity there is a coin flip, not a claim), then
    the same workload runs through both engines. Asserted: >= 1.5x peak
    concurrent resident requests for int8 at equal pool bytes, and greedy
    outputs token-for-token identical — the residency win may not cost
    tokens."""
    from repro.core.quant import sharpen_for_parity
    from repro.serving.kv_cache import kv_page_bytes

    cfg = smoke_config(get_config("llama3-e8t2"))
    cfg = cfg.replace(moe=dataclasses.replace(
        cfg.moe, capacity_factor=None, dispatcher="allgather"))
    params = init_from_decls(model_decl(cfg), jax.random.PRNGKey(0))
    params, pattern = sharpen_for_parity(cfg, params)

    budget = QUANT_PAGES_BF16 * kv_page_bytes(cfg, PAGE_SIZE)
    q8_page = kv_page_bytes(cfg.replace(quant_kv="int8"), PAGE_SIZE)
    pages = {"bf16": QUANT_PAGES_BF16, "int8": budget // q8_page}

    def _requests():
        # rotations of the memorized pattern, limited to the rolls the probe
        # actually trained on (sharpen_for_parity's batch of 8): only there
        # do the top-1 margins provably dwarf the int8 error. rid 8+ repeat
        # the prompts — duplicate traffic, realistic and margin-safe.
        return [
            Request(rid=i,
                    prompt=np.roll(pattern, -(i % 8))[:QUANT_PROMPT]
                    .astype(np.int32),
                    max_new_tokens=QUANT_NEW)
            for i in range(QUANT_REQS)
        ]

    engines, outs = {}, {}
    for tag, quant in (("bf16", "none"), ("int8", "int8")):
        engine = ServingEngine(
            cfg, params, max_batch=QUANT_REQS, max_seq=MAX_SEQ,
            cache_mode="paged", page_size=PAGE_SIZE,
            prefill_chunk=PREFILL_CHUNK, num_pages=pages[tag],
            quant_kv=quant,
        )
        stats, outs[tag] = drive(engine, _requests())
        kv = engine.kv_stats()
        engine.page_pool.check_invariants()
        assert engine.page_pool.free_pages == engine.page_pool.num_pages
        engines[tag] = {
            "num_pages": pages[tag],
            "pool_bytes": pages[tag] * kv_page_bytes(engine.cfg, PAGE_SIZE),
            "page_bytes": kv_page_bytes(engine.cfg, PAGE_SIZE),
            "tokens_per_s": stats["tokens_per_s"],
            "kv_bytes_resident_peak": stats["kv_bytes_resident"],
            "peak_resident_requests": int(kv["peak_resident_requests"]),
        }
    parity = outs["bf16"] == outs["int8"]
    assert parity, "int8 KV pages changed greedy tokens on the probe model"
    ratio = (engines["int8"]["peak_resident_requests"]
             / max(engines["bf16"]["peak_resident_requests"], 1))
    assert ratio >= 1.5, (
        f"int8 pages admitted only {ratio:.2f}x the resident requests of "
        f"bf16 at equal pool bytes (need >= 1.5x): {engines}"
    )
    print(f"  quant-kv: {engines['int8']['num_pages']} int8 pages vs "
          f"{engines['bf16']['num_pages']} bf16 in {budget/1e6:.2f} MB, "
          f"resident requests {engines['int8']['peak_resident_requests']} vs "
          f"{engines['bf16']['peak_resident_requests']} ({ratio:.2f}x), "
          f"parity={parity}")
    return {
        "workload": {
            "requests": QUANT_REQS, "prompt_len": QUANT_PROMPT,
            "max_new": QUANT_NEW, "max_batch": QUANT_REQS,
            "page_size": PAGE_SIZE, "prefill_chunk": PREFILL_CHUNK,
        },
        "pool_bytes_budget": budget,
        "engines": engines,
        "resident_requests_ratio_int8": round(ratio, 2),
        "parity_token_for_token": parity,
    }


# -- multi-device scaling (subprocess workers) -------------------------------
# pool-bound workload: every request needs 5 pages (24-token prompt + 8 new
# at page_size 8) and each DP shard's sub-pool holds 11, so exactly two
# requests fit a shard concurrently — peak resident requests then scales
# with the shard count, which is the aggregate-pool claim under test.
SCALE_PROMPT, SCALE_NEW, SCALE_PPS = 24, 8, 11
SCALE_TOPOLOGIES = [  # (devices, dp, ep)
    (1, 1, 1),
    (2, 2, 1),
    (4, 4, 1),
    (4, 2, 2),  # EP x DP: decode through the overlapped expert all-to-all
]


def _bench_cfg():
    cfg = smoke_config(get_config("llama3-e8t2")).replace(dtype="float32")
    # dropless so chunked prefill routing matches full prefill routing
    return cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=None))


def scaling_worker(dp: int, ep: int) -> None:
    """Run the pool-bound workload on a dp x ep serving mesh; prints one
    JSON row (parsed by the parent from the last stdout line)."""
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.kv_cache import kv_bytes_resident_per_shard

    cfg = _bench_cfg()
    params = init_from_decls(model_decl(cfg), jax.random.PRNGKey(0))
    engine = ServingEngine(
        cfg, params, max_batch=4 * dp, max_seq=MAX_SEQ, cache_mode="paged",
        page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK,
        num_pages=SCALE_PPS * dp, mesh=make_serving_mesh(dp, ep),
    )
    rng = np.random.default_rng(7)
    requests = [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab_size, SCALE_PROMPT).astype(np.int32),
                max_new_tokens=SCALE_NEW)
        for i in range(N_REQ)
    ]
    for r in requests:
        engine.submit(r)
    per_shard_peak = [0] * engine.dp_shards
    t0 = time.perf_counter()
    while engine.sched.has_work:
        engine.step()
        for s, b in enumerate(kv_bytes_resident_per_shard(cfg, engine.page_pool)):
            per_shard_peak[s] = max(per_shard_peak[s], b)
    wall = time.perf_counter() - t0
    engine.page_pool.check_invariants()
    assert engine.page_pool.free_pages == engine.page_pool.num_pages, "pool leak"
    kv = engine.kv_stats()
    total = sum(len(r.output) for r in requests)
    print(json.dumps({
        "devices": dp * ep, "dp": dp, "ep": ep,
        "dispatcher": engine.cfg.moe.dispatcher,
        "tokens": total,
        "tokens_per_s": round(total / wall, 2),
        "kv_bytes_resident_peak": int(kv["kv_bytes_peak"]),
        "kv_bytes_resident_per_shard_peak": per_shard_peak,
        "peak_resident_requests": int(kv["peak_resident_requests"]),
        "outputs": {str(r.rid): list(map(int, r.output)) for r in requests},
    }))


def run_scaling():
    """Launch one subprocess per topology and build the ``scaling`` report
    section (the parent process has already initialized jax at one device,
    so fake-device runs must be fresh processes).

    The children start after the parent has touched JAX. That is safe only
    because each child is pinned to the CPU backend (``JAX_PLATFORMS=cpu``
    below): on a machine with a TPU the parent holds the chip, and a child
    that reached for it would fail or hang. Keep the pin."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows = []
    for devices, dp, ep in SCALE_TOPOLOGIES:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in [os.path.join(root, "src"), root,
                        env.get("PYTHONPATH", "")] if p
        )
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--scaling-worker", str(dp), str(ep)],
            capture_output=True, text=True, env=env, cwd=root, timeout=1800,
        )
        assert proc.returncode == 0, (
            f"scaling worker dp={dp} ep={ep} failed:\n{proc.stdout}\n{proc.stderr}"
        )
        rows.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        r = rows[-1]
        print(f"  scaling dp={dp} ep={ep}: {r['tokens_per_s']} tok/s, "
              f"peak resident requests {r['peak_resident_requests']}, "
              f"peak KV/shard {r['kv_bytes_resident_per_shard_peak']}")

    base = next(r for r in rows if r["devices"] == 1)
    two = next(r for r in rows if r["devices"] == 2)
    ratio = two["peak_resident_requests"] / max(base["peak_resident_requests"], 1)
    assert ratio >= 1.8, (
        f"2-device aggregate pool admitted only {ratio:.2f}x the resident "
        f"requests of 1 device (need >= 1.8x)"
    )
    for r in rows:
        # no single shard's peak exceeds the aggregate peak, and EP x DP
        # decode emits exactly the single-device token streams
        assert max(r["kv_bytes_resident_per_shard_peak"]) <= r[
            "kv_bytes_resident_peak"
        ], r
        r["ep_decode_parity"] = r["outputs"] == base["outputs"]
        assert r["ep_decode_parity"], f"decode parity broken at dp={r['dp']} ep={r['ep']}"
    for r in rows:
        del r["outputs"]  # bulky; parity already folded into the flag
    return {
        "workload": {
            "requests": N_REQ, "prompt_len": SCALE_PROMPT,
            "max_new": SCALE_NEW, "pages_per_shard": SCALE_PPS,
            "page_size": PAGE_SIZE, "prefill_chunk": PREFILL_CHUNK,
        },
        "rows": rows,
        "resident_requests_scaling_2dev": round(ratio, 2),
    }


def main():
    cfg = _bench_cfg()
    # single-host sections have no EP plan: pick the legal dispatcher
    # explicitly rather than riding the quiet alltoall->allgather fallback,
    # which CI's REPRO_STRICT_DISPATCH=1 turns into a loud error
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, dispatcher="allgather"))
    params = init_from_decls(model_decl(cfg), jax.random.PRNGKey(0))

    rows, outputs = [], {}
    for mode, kw in [
        ("ring", {}),
        ("paged", dict(page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK)),
    ]:
        engine = ServingEngine(cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                               cache_mode=mode, **kw)
        stats, outs = drive(engine, make_requests(cfg))
        stats["mode"] = mode
        rows.append(stats)
        outputs[mode] = outs

    ring, paged = rows[0], rows[1]
    parity = outputs["ring"] == outputs["paged"]
    assert parity, "greedy parity violated between ring and paged engines"
    assert paged["kv_bytes_resident"] <= ring["kv_bytes_resident"], (
        "paged mode must not pin more KV than the dense ring cache"
    )

    keys = ["mode", "tokens", "tokens_per_s", "p50_ms", "p99_ms",
            "kv_bytes_resident", "page_utilization", "peak_used_pages",
            "num_pages", "prefill_traces"]
    emit("serving_bench", rows, keys)
    report = {
        "config": cfg.name,
        "workload": {
            "requests": N_REQ, "max_new": MAX_NEW, "max_batch": MAX_BATCH,
            "max_seq": MAX_SEQ, "page_size": PAGE_SIZE,
            "prefill_chunk": PREFILL_CHUNK,
        },
        "engines": {r["mode"]: {k: r[k] for k in keys if k != "mode"} for r in rows},
        "parity_token_for_token": parity,
        "kv_bytes_saved": ring["kv_bytes_resident"] - paged["kv_bytes_resident"],
    }
    report["resilience"] = run_resilience(cfg, params)
    res = report["resilience"]
    print(f"overload resilience: {res['accepted']} accepted / "
          f"{res['shed_count']} shed, {res['deadline_evictions']} deadline "
          f"evictions, {res['completed_ok']} completed on time")
    print("prefix-cache KV reuse...")
    report["prefix_reuse"] = run_prefix_reuse(cfg, params)
    print("dense-parent speculative decoding...")
    report["speculation"] = run_speculation(cfg)
    print("quantized KV pages at fixed pool bytes (sharpening probe model)...")
    report["quant"] = run_quant_kv()
    if "--skip-scaling" not in sys.argv:
        print("multi-device scaling (subprocess workers)...")
        report["scaling"] = run_scaling()
    with open(ROOT_JSON, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {ROOT_JSON}")
    print(f"paged pins {paged['kv_bytes_resident']/1e6:.2f} MB peak vs ring "
          f"{ring['kv_bytes_resident']/1e6:.2f} MB "
          f"({report['kv_bytes_saved']/1e6:.2f} MB saved), parity={parity}")
    if "scaling" in report:
        print(f"resident-request scaling at 2 devices: "
              f"{report['scaling']['resident_requests_scaling_2dev']}x")


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--scaling-worker":
        scaling_worker(int(sys.argv[2]), int(sys.argv[3]))
    else:
        main()
