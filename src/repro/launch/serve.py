"""Serving launcher: batched greedy decoding with the ServingEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-30b-a3b --smoke \\
      --requests 8 --max-new 24 --cache-mode paged --page-size 16 \\
      --prefill-chunk 32
  # published widths, depth cut to 2 layers (one TPU v5e):
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-e8t2 --layers 2 \\
      --cache-mode paged --dispatcher sorted --use-kernel
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.config import get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import model_decl
from repro.serving.engine import Request, ServingEngine
from repro.sharding.rules import init_from_decls


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="override num_layers (every width stays as "
                         "published): the one-chip depth cut")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--dispatcher", default=None,
        choices=["allgather", "alltoall", "a2a_overlap", "sorted"],
        help="MoE token dispatcher for decode (default: config's choice; "
        "mesh mode defaults to the overlapped EP exchange)",
    )
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel shards (per-shard KV sub-pools)")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel shards for MoE decode")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument(
        "--cache-mode", default="ring", choices=["ring", "paged"],
        help="KV cache backend: dense ring buffer or block-table page pool",
    )
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size (default: ring-capacity parity)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens prefetched per chunked-prefill step")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="evict requests older than this many engine steps "
                         "(0 = no deadlines; paged mode only)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="shed submits past this queue depth with a typed "
                         "ShedError (0 = unbounded)")
    ap.add_argument("--shed-watermark", type=int, default=0,
                    help="shed submits when free KV pages minus backlog dip "
                         "below this reserve (0 = off; paged mode only)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over the page pool: requests "
                         "sharing a prompt stem reuse its KV pages (paged "
                         "mode only; the demo prompts share a stem so the "
                         "cache actually hits)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per step on "
                         "a dense drafter, verify in one step (paged mode "
                         "only). For an MoE --arch the drafter is its dense "
                         "parent and the served params are upcycled from it "
                         "(the paper's function-preserving pair); otherwise "
                         "the drafter self-speculates with the same params "
                         "unless --draft-arch says otherwise")
    ap.add_argument("--draft-arch", default=None,
                    help="drafter architecture for --speculate (must share "
                         "the tokenizer/vocab; independently initialized, so "
                         "expect low acceptance — a correctness demo)")
    ap.add_argument("--quant-weights", default="none",
                    choices=["none", "int8"],
                    help="serve int8 expert-FFN weights (per-channel scales, "
                         "dequant fused into the Pallas epilogue)")
    ap.add_argument("--quant-kv", default="none", choices=["none", "int8"],
                    help="int8 KV pages with a per-token scale sidecar "
                         "(requires --cache-mode paged)")
    ap.add_argument("--fused-dispatch", action="store_true",
                    help="dispatch-in-kernel MoE decode: the sorted "
                         "dispatcher's gather/combine run inside the "
                         "grouped-GEMM kernel (requires --use-kernel; "
                         "implies --dispatcher sorted)")
    ap.add_argument("--autotune", action="store_true",
                    help="enable the roofline-driven Pallas tile autotuner "
                         "(sets REPRO_AUTOTUNE=1; winners persist in "
                         "~/.cache/repro_autotune.json)")
    args = ap.parse_args(argv)
    if args.autotune:
        os.environ["REPRO_AUTOTUNE"] = "1"  # before any kernel wrapper runs
    if (args.speculate or args.prefix_cache) and args.cache_mode != "paged":
        ap.error("--speculate/--prefix-cache require --cache-mode paged")
    if args.quant_kv != "none" and args.cache_mode != "paged":
        ap.error("--quant-kv requires --cache-mode paged (the scale sidecar "
                 "lives in the page pool)")
    if args.fused_dispatch:
        if not args.use_kernel:
            ap.error("--fused-dispatch requires --use-kernel (the fusion "
                     "lives in the Pallas grouped GEMM)")
        if args.dispatcher not in (None, "sorted"):
            ap.error("--fused-dispatch requires --dispatcher sorted")
        args.dispatcher = "sorted"

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    if (args.layers and cfg.moe is not None and args.dp * args.ep == 1
            and (args.dispatcher or cfg.moe.dispatcher)
            in ("alltoall", "a2a_overlap")):
        ap.error(f"{args.arch} defaults to the {cfg.moe.dispatcher!r} EP "
                 "dispatcher, which needs an EP mesh (--ep); on one device "
                 "name --dispatcher sorted")
    if cfg.family == "vlm":
        # serving demo drives the text path; image prefix handled at prefill
        cfg = cfg.replace(num_prefix_embeds=0, family="dense")
    params = init_from_decls(model_decl(cfg), jax.random.PRNGKey(args.seed))
    mesh = None
    if args.dp > 1 or args.ep > 1:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.dp, args.ep)
    common = dict(max_batch=args.max_batch,
                  max_seq=args.prompt_len + args.max_new + 8,
                  dispatcher=args.dispatcher, use_kernel=args.use_kernel,
                  cache_mode=args.cache_mode, page_size=args.page_size,
                  num_pages=args.num_pages,
                  prefill_chunk=args.prefill_chunk, mesh=mesh,
                  deadline_steps=args.deadline_steps or None,
                  max_queue=args.max_queue or None,
                  shed_watermark=args.shed_watermark or None,
                  prefix_cache=args.prefix_cache,
                  quant_weights=args.quant_weights, quant_kv=args.quant_kv,
                  fused_dispatch=args.fused_dispatch)
    if args.speculate:
        from repro.serving.speculative import SpeculativeEngine

        if args.draft_arch is not None:
            dcfg = get_config(args.draft_arch)
            if args.smoke:
                dcfg = smoke_config(dcfg)
            dparams = init_from_decls(model_decl(dcfg), jax.random.PRNGKey(args.seed + 1))
            engine = SpeculativeEngine(cfg, params, dcfg, dparams,
                                       draft_k=args.speculate, **common)
        elif cfg.moe is not None:
            # the paper's pairing: serve params upcycled from the dense
            # parent, draft on the parent itself (function-preserving init
            # -> near-100% acceptance)
            dense_cfg = cfg.replace(name=f"{cfg.name}-parent", family="dense",
                                    moe=None)
            dense_params = init_from_decls(
                model_decl(dense_cfg), jax.random.PRNGKey(args.seed)
            )
            engine = SpeculativeEngine.from_upcycle(
                dense_cfg, cfg, dense_params, draft_k=args.speculate, **common
            )
        else:
            engine = SpeculativeEngine(cfg, params, cfg, params,
                                       draft_k=args.speculate, **common)
    else:
        engine = ServingEngine(cfg, params, **common)
    rng = np.random.default_rng(args.seed)

    def _prompt():
        return rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32)

    stem = _prompt()[: args.prompt_len // 2]  # shared head for --prefix-cache
    reqs = [
        Request(rid=i,
                prompt=(np.concatenate([stem, _prompt()[len(stem):]])
                        if args.prefix_cache else _prompt()),
                max_new_tokens=args.max_new)
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    from repro.resilience import ShedError

    accepted, shed = [], 0
    for r in reqs:
        try:
            engine.submit(r)
            accepted.append(r)
        except ShedError as e:
            shed += 1
            print(f"  SHED: {e}")
    outputs = {r.rid: r.output for r in accepted}
    steps = 0
    while steps < 10_000 and (
        engine.sched.has_work if args.cache_mode == "paged"
        else (any(engine.slots) or engine.queue)
    ):
        engine.step()
        steps += 1
    dt = time.perf_counter() - t0
    total_tokens = sum(len(v) for v in outputs.values())
    print(f"served {len(accepted)} requests ({shed} shed), {total_tokens} "
          f"tokens in {dt:.2f}s ({total_tokens/dt:.1f} tok/s, "
          f"batch={args.max_batch}, cache={args.cache_mode})")
    if args.quant_weights != "none" or args.quant_kv != "none":
        print(f"  quant: weights={args.quant_weights}, kv={args.quant_kv} "
              f"(int8 payloads, fp32 accumulate, scales in sidecars)")
    h = engine.health()
    expired = [r.rid for r in accepted if r.status == "deadline"]
    if expired:
        print(f"  deadline-evicted requests: {expired}")
    print(f"  health: shed {h['shed_count']}, deadline evictions "
          f"{h['deadline_evictions']}, queued {h['queued_requests']}, "
          f"resident {h['resident_requests']}")
    kv = engine.kv_stats()
    print(f"  kv peak {kv['kv_bytes_peak']/1e6:.2f} MB"
          + (f", page util {kv['page_utilization']:.2f}, "
             f"peak pages {kv['peak_used_pages']}/{kv['num_pages']}"
             if args.cache_mode == "paged" else ""))
    if args.prefix_cache:
        p = kv["prefix"]
        print(f"  prefix cache: {p['hits']}/{p['lookups']} hits, "
              f"{p['hit_tokens']} prompt tokens served from cache, "
              f"{p['cow_clones']} COW clones, "
              f"{p['resident_pages']} pages resident")
    if args.speculate:
        s = kv["speculation"]
        print(f"  speculation: k={s['draft_k']}, acceptance "
              f"{s['acceptance_rate']:.2%} "
              f"({s['accepted_tokens']}/{s['drafted_tokens']} drafts over "
              f"{s['spec_steps']} verify steps)")
    for rid, out in sorted(outputs.items())[:4]:
        print(f"  req {rid}: {out[:12]}{'...' if len(out) > 12 else ''}")
    return outputs


if __name__ == "__main__":
    main()
