#!/usr/bin/env python3
"""Knee sweep of a serving cell, on the chip: the cell's open loop at each
of several fixed rates, one after another in one process on one set of
weights, each on a fresh engine (an empty pool and queue) with the mix's
population in flight for that rate, its pre-roll and a window of
``--seconds``. The benchmark's own runs never run this; it finds once the
highest rate the program sustains, from which the mix's ``rate_per_s`` is
set, and the residence time from which its ``residence_s`` is set.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds 60 --rates 0.9 1.1 1.3 2.0 [--residence-s 40]

One JSON line per rate: the window's TTFT and ITL medians and tails with
their sample counts, the share of the requests due in the window that met
both of the mix's limits, the backlog (queued and running requests) every
``--sample-s`` seconds of the window, the answer tokens served per second
and the rate of requests they make (over the mix's mean answer), the mean
engine step (arrivals and step, idle waits left out), and the residence
time it gives (``serve_gen.
steps_per_request`` steps of that length). A rate is sustained where the
backlog does not grow over the window; above the knee the slots stay full
and the served rate is the program's capacity.
"""
import argparse
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--residence-s", type=float, default=None,
                    help="the population's residence time (default: the mix's)")
    ap.add_argument("--sample-s", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    import numpy as np
    from repro.serving.engine import ServingEngine

    from benchmarks.chip import bench, harness, serve_gen, serve_loop

    cell = bench.load_cell(args.workload)
    harness.devices(cell.chips)
    harness.enable_cache()
    engine, d = serve_loop.build(cell, args.seed)
    params = engine.params
    t = cell.traffic
    mean_answer = float(serve_gen.lognormal_lengths(t["output"], 4096).mean())
    steps_per = serve_gen.steps_per_request(t, cell.config["run"]["prefill_chunk"])
    serve_loop.run_checked(engine, serve_loop.Calls(engine),
                           serve_gen.checked_prompts(t, args.seed, d.vocab)[:1], 1)
    for rate in args.rates:
        mix = dict(t, rate_per_s=rate)
        if args.residence_s is not None:
            mix["residence_s"] = args.residence_s
        calls = serve_loop.Calls(engine)
        w0, w1 = mix["preroll_s"], mix["preroll_s"] + args.seconds
        pop = serve_gen.population(mix, args.seed, d.vocab)
        loop = serve_loop.Loop(engine, serve_gen.schedule(mix, args.seed, args.seconds, d.vocab),
                               last_due=w1, population=pop)
        loop.run(w0)
        backlog = [[0.0, len(engine.sched.queue), len(engine.sched.running)]]
        steps0, busy0 = loop.steps, loop.step_s
        calls.on = True
        for k in range(1, int(np.ceil(args.seconds / args.sample_s)) + 1):
            loop.run(min(w0 + k * args.sample_s, w1))
            backlog.append([loop.now() - w0, len(engine.sched.queue), len(engine.sched.running)])
        calls.on = False
        steps, busy = loop.steps - steps0, loop.step_s - busy0
        due = [s for s in loop.served if w0 <= s.plan.due_s < w1]
        loop.run(w1 + mix["drain_limit_s"], stop=lambda: all(s.times or s.refused for s in due))
        tokens = sum(1 for s in loop.served for x in s.times if w0 <= x < w1)
        st = serve_loop.window_stats(loop, w0, w1, mix)
        step_s = busy / max(steps, 1)
        print(json.dumps({
            "rate_per_s": rate, "residence_s": mix["residence_s"],
            "population": len(pop),
            "backlog_t_queued_running": backlog,
            "answer_tokens_per_s": tokens / args.seconds,
            "served_rate_per_s": tokens / args.seconds / mean_answer,
            "engine_steps": steps, "step_ms": step_s * 1e3,
            "steps_by_chunks": serve_loop.chunk_histogram(calls.step_chunks),
            "residence_s_measured": steps_per * step_s,
            "ttft_samples": len(st.pop("ttft_ms")), "itl_samples": len(st.pop("itl_ms")),
            **st}), flush=True)
        harness.free(engine.pool_dev)
        engine = ServingEngine(engine.cfg, params, **serve_loop.engine_kwargs(cell))
    return 0


if __name__ == "__main__":
    sys.exit(main())
