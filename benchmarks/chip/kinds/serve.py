"""Traffic kind ``serve``: an open loop of chat requests into the program's
serving engine, judged on time to first token and the gaps between tokens.

The engine is built as ``launch/serve.py`` builds it (``ServingEngine``
with the configuration's cache mode, page size, pool, prefill chunk,
dispatcher and kernels) on weights made from the seed and upcycled by the
program, with each expert diverged from the dense FFN (``serve_gen.py``).

Set-up builds the engine and drives it through the checked requests
(``checked`` in the mix: prompts of several chunks, a final prefill chunk
and then decode steps through the paged cache), recording the logits that
the engine's own prefill-chunk and decode programs return. Those calls
compile or load every program the window uses. A request stays in flight
far longer than the window (hundreds of decode steps), so set-up then
puts in the population a server holds in steady state at the mix's rate
(``serve_gen.population``: rate x the residence time measured on the
chip, requests part-way through their answers) and prefills it. Then
the arrivals start: requests due at the times ``serve_gen.schedule``
draws, submitted by the loop as they fall due, for a pre-roll of
``preroll_s`` (set-up) and the measured window of ``seconds``. After the window no request is sent and the engine runs on,
untimed, until every request due in the window has its first token
(``drain_limit_s`` at most).

Time to first token is counted from a request's due time, so a late loop
or a stalled step shows; a token's time is the host clock when the engine
step that made it returned. ``ttft_mean_ms`` is the mean over the
requests due in the window (about ten at the chat mix's rate: too few
for a tail, and a median of ten is two of them, each a step early or
late as its arrival meets the engine's steps), ``itl_p95_ms`` the 95th
percentile over the gaps between consecutive tokens of a request whose
second token came in the window (thousands). A request refused at
submit or never answered is failed and misses both limits (inf).

Once the engine is freed, the plain reference (``reference_serve.py``)
runs over the checked requests and a sample of the requests the run
served tokens for (``served_sample``, the one with the most among them;
few answers end within a run) and the run compares, where
the cell's limits name them: ``checked_logit_gap_mean``, the mean over
the checked positions of the relative L2 gap of the engine's logits
against the reference's; ``served_gap_mean``, the mean by which a served
token's reference logit lies below the reference's best. The widest of
each (``checked_logit_gap``, ``served_gap_max``) is printed beside them:
a near-tie of the router's second and third expert, which rounding turns
either way, sets it (PERF.md §2).
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Dict

from benchmarks.chip import bench, flops_serve, harness, serve_gen, serve_loop, serve_trace
from benchmarks.chip.serve_loop import Calls, Loop, build, compare, describe, run_checked, window_stats


def serve(cell: bench.Cell, seed: int, seconds: float, trace_on: bool,
          t_start: float, devs) -> Dict:
    """Set-up, window and drain; the engine freed. Returns the window's
    numbers, the checked and the sampled requests, and the context for the
    per-layer readers."""
    t = cell.traffic
    engine, d = build(cell, seed)
    calls = Calls(engine)
    checked = run_checked(engine, calls, serve_gen.checked_prompts(t, seed, d.vocab),
                          t["checked"]["decode_steps"])

    w0, w1 = t["preroll_s"], t["preroll_s"] + seconds
    loop = Loop(engine, serve_gen.schedule(t, seed, seconds, d.vocab), last_due=w1,
                population=serve_gen.population(t, seed, d.vocab))
    loop.run(w0)
    backlog0, steps0 = loop.backlog(), loop.steps
    setup_s = time.time() - t_start
    calls.on = True
    with harness.traced(trace_on) as tr_file:
        with harness.span("bench.window"):
            loop.run(w1)
    calls.on = False
    window_steps, backlog1 = loop.steps - steps0, loop.backlog()
    due = [s for s in loop.served if w0 <= s.plan.due_s < w1]
    loop.run(w1 + t["drain_limit_s"], stop=lambda: all(s.times or s.refused for s in due))
    stats = window_stats(loop, w0, w1, t)
    mem = harness.peak_memory(devs)
    print(f"serve: {describe(stats)}; backlog {backlog0} at the window's start, "
          f"{backlog1} at its end; {len(calls.chunks)} prefill chunks and "
          f"{len(calls.decodes)} decode steps in the window; engine steps by prefill "
          f"chunks run {serve_loop.chunk_histogram(calls.step_chunks)}", file=sys.stderr)
    modules = serve_trace.load_modules(tr_file.path) if tr_file.path else {}

    done = [s for s in loop.served if s.req is not None and s.req.output]
    pick = serve_gen.sample_served([s.plan.idx for s in done],
                                   [len(s.req.output) for s in done],
                                   t["served_sample"], seed)
    by_idx = {s.plan.idx: s for s in done}
    sampled = {i: {"prompt": by_idx[i].plan.prompt, "output": list(by_idx[i].req.output)}
               for i in pick}
    harness.free(engine.params)
    harness.free(engine.pool_dev)
    del engine, calls.engine
    gc.collect()
    return {"stats": stats, "setup_s": setup_s, "checked": checked, "sampled": sampled,
            "dims": d, "ctx": {
                "kind": "serve", "dims": d, "page_size": cell.config["run"]["page_size"],
                "chunks": calls.chunks, "decodes": calls.decodes,
                "chunk_steps": sum(1 for n in calls.step_chunks if n), "steps": window_steps,
                "modules": modules, "trace_path": tr_file.path, "trace_dir": tr_file.dir,
                "memory_peak_bytes": mem}}


def run(cell: bench.Cell, seed: int, seconds: float, trace_on: bool,
        t_start: float, devs) -> harness.Run:
    out = serve(cell, seed, seconds, trace_on, t_start, devs)
    checked, sampled, stats = out["checked"], out["sampled"], out["stats"]
    t0 = time.perf_counter()
    nums = compare(seed, out["dims"], serve_gen.delta_std(cell.config), checked, sampled)
    print(f"serve: reference over {len(checked)} checked and {len(sampled)} sampled "
          f"requests ({nums['served_tokens']} served tokens) in "
          f"{time.perf_counter() - t0!r} s; " + ", ".join(f"{k} {v!r}" for k, v in nums.items()),
          file=sys.stderr)
    lim = cell.limits
    return harness.Run(
        e2e={"ttft_mean_ms": stats["ttft_mean_ms"], "itl_p95_ms": stats["itl_p95_ms"],
             "setup_s": out["setup_s"]},
        checks={k: (nums[k], lim[k]) for k in lim},
        attempted=stats["requests"], failed=stats["failed"], ctx=out["ctx"],
    )


def read_trace(ctx: Dict, device: Dict) -> Dict:
    """The engine's two programs in the traced window
    (``serve_trace.programs``, found by how often the scheduler's plans
    ran them; it raises where the trace does not show one that ran, and
    this where the one found ran faster than its calls' expert GEMMs
    could, so a small program that ran as often is never taken): runs,
    ms per run and the largest op families of each, printed to standard
    error and kept as ``programs`` for the readers."""
    progs = serve_trace.programs(ctx["trace"], ctx["modules"], ctx["lo"], ctx["hi"],
                                 decodes=len(ctx["decodes"]),
                                 chunk_calls=(len(ctx["chunks"]), ctx["chunk_steps"]))
    d = ctx["dims"]
    gemm = {"decode": [flops_serve.expert_gemm(d, len(lens)) for lens in ctx["decodes"]],
            "prefill": [flops_serve.expert_gemm(d, n) for _, n, _ in ctx["chunks"]]}
    for name, p in progs.items():
        least = flops_serve.least_seconds(ctx, gemm[name])
        if least > 1.05 * p["self_ns"] * 1e-9:
            raise RuntimeError(f"the program that ran as often as the {name} calls took "
                               f"{p['self_ns'] * 1e-9!r} s, under the least time of their "
                               f"expert GEMMs alone ({least!r} s): it is not the {name} program")
    for name, p in progs.items():
        top = sorted(p["families"].items(), key=lambda kv: -kv[1])[:6]
        print(f"trace: {name} {p['runs']} runs, {p['self_ns'] * 1e-6 / max(p['runs'], 1)!r} ms "
              f"per run; " + ", ".join(f"{f} {ns * 1e-6 / p['runs']!r}" for f, ns in top),
              file=sys.stderr)
    print(f"trace: idle {(device['window_s'] - device['busy_s']) * 1e3!r} ms of "
          f"{device['window_s'] * 1e3!r} ms; {ctx['steps']} engine steps", file=sys.stderr)
    return {**ctx, "programs": progs}


def rehearse(cell: bench.Cell, sharding):
    """The timed programs compiled for ``sharding`` from shapes alone (see
    ``rehearse.py``): the weight build, then ``programs``."""
    import jax
    import jax.numpy as jnp

    dense_cfg, moe_cfg = bench.model_config(cell.config)
    d = bench.dims(cell.config, moe_cfg.padded_vocab)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding)
    yield "weights", serve_loop.make_init(
        dense_cfg, moe_cfg, d, serve_gen.delta_std(cell.config)).lower(key).compile()
    yield from programs(cell, sharding)


def programs(cell: bench.Cell, sharding):
    """The engine's prefill-chunk and decode programs, each jitted as
    ``ServingEngine`` jits it and compiled for ``sharding`` at the cell's
    batch, chunk, block table and pool."""
    import jax
    import jax.numpy as jnp

    from repro.config import with_dispatcher
    from repro.models.model import decode_step_paged, model_decl, paged_forward, paged_stack_decl
    from repro.sharding.rules import abstract_from_decls

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
                            tree)

    kw = serve_loop.engine_kwargs(cell)
    _, moe_cfg = bench.model_config(cell.config)
    cfg = with_dispatcher(moe_cfg, kw["dispatcher"])
    params = abstract(abstract_from_decls(model_decl(cfg)))
    pool = abstract(abstract_from_decls(
        paged_stack_decl(cfg, kw["num_pages"] + 1, kw["page_size"])))
    B, C = kw["max_batch"], kw["prefill_chunk"]
    maxP = -(-kw["max_seq"] // kw["page_size"])
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
    chunk = jax.jit(lambda p, pool, t, s, bt, vl, tr: paged_forward(
        cfg, None, p, pool, t, s, bt, vl, use_kernel=kw["use_kernel"], trash_page=tr),
        donate_argnums=(1,))
    yield "prefill chunk", chunk.lower(params, pool, i32(1, C), i32(1), i32(1, maxP), i32(1),
                                       i32(1)).compile()
    decode = jax.jit(lambda p, pool, t, pos, bt, a, tr: decode_step_paged(
        cfg, None, p, pool, t, pos, bt, a, use_kernel=kw["use_kernel"], trash_page=tr),
        donate_argnums=(1,))
    yield "decode", decode.lower(params, pool, i32(B), i32(B), i32(B, maxP), i32(B),
                                 i32(B)).compile()
