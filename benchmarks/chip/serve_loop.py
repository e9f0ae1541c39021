"""The serving engine as the ``serve`` kind drives it: its weights, the
engine built as ``launch/serve.py`` builds it, what the benchmark records
of its calls, the open loop that feeds it, and the comparison with the
plain reference. ``kinds/serve.py`` runs a cell with these; ``sweep.py``
and ``control_serve.py`` use them on the chip.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmarks.chip import bench, gen, harness, serve_gen

CHECKED_RID = 1 << 30  # the checked requests' ids, above any scheduled one


def make_init(dense_cfg, moe_cfg, d: gen.Dims, std: float):
    """A jitted ``build(seed_key) -> MoE params``: ``weights.make_init``'s
    dense parent, upcycling and router, then each expert leaf replaced by
    its diverged value (``serve_gen.diverged``), all in one call."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import weights

    base = weights.make_init(dense_cfg, moe_cfg, d)

    def build(key):
        moe = base(key)
        ex = moe["stack"]["slot0"]["ffn"]["experts"]
        for name in serve_gen.EXPERT_LEAVES:
            w = ex[name]  # (layers, experts, ...) in the served type
            ex[name] = jnp.stack([jnp.stack([
                (w[l, e].astype(jnp.float32)
                 + serve_gen.expert_delta(key, d, l, e, name, std)).astype(w.dtype)
                for e in range(d.num_experts)]) for l in range(d.num_layers)])
        return moe

    return jax.jit(build)


def engine_kwargs(cell: bench.Cell) -> Dict:
    run, t = cell.config["run"], cell.traffic
    return dict(
        max_batch=run["max_batch"], max_seq=t["prompt"]["max"] + t["output"]["max"],
        dispatcher=cell.config["upcycle"]["dispatcher"], use_kernel=run["use_kernel"],
        cache_mode=run["cache_mode"], page_size=run["page_size"],
        num_pages=run["num_pages"], prefill_chunk=run["prefill_chunk"],
    )


def build(cell: bench.Cell, seed: int):
    """The engine on the seed's weights, and the benchmark's dims."""
    from repro.serving.engine import ServingEngine

    from benchmarks.chip import weights

    dense_cfg, moe_cfg = bench.model_config(cell.config)
    d = bench.dims(cell.config, moe_cfg.padded_vocab)
    params = make_init(dense_cfg, moe_cfg, d, serve_gen.delta_std(cell.config))(
        gen.seed_key(seed))
    weights.check_tree(params, moe_cfg)
    return ServingEngine(moe_cfg, params, **engine_kwargs(cell)), d


class Calls:
    """What the engine's steps ran, read from the scheduler's plan of each
    step (``ChunkedScheduler.plan``, which every engine step calls once):
    while ``on``, each prefill chunk (start, length, final), each decode
    call's context lengths and each step's number of chunks. While
    ``capture`` names request ids, the logits rows that the engine's
    compiled prefill-chunk and decode programs returned for them, each row
    matched to its request by the call's own arguments (the first page of
    a row's block table, its start and length; the active decode rows).
    The engine runs unchanged underneath."""

    def __init__(self, engine):
        self.engine = engine
        self.on = False
        self.chunks: List[tuple] = []
        self.decodes: List[List[int]] = []
        self.step_chunks: List[int] = []
        self.capture: Optional[Dict[int, list]] = None
        sched = engine.sched
        plan, chunk_fn, decode_fn = sched.plan, engine._chunk_fn, engine._decode_paged

        def planned(*a, **kw):
            p = plan(*a, **kw)
            if self.on:
                self.chunks += [(c.start, c.length, c.final) for c in p.prefills]
                self.step_chunks.append(len(p.prefills))
                if p.decode_slots:
                    self.decodes.append([sched.running[s].decode_pos + 1
                                         for s in p.decode_slots])
            return p

        def chunk(p, pool, toks, start, bt, vlen, trash):
            out = chunk_fn(p, pool, toks, start, bt, vlen, trash)
            if self.capture is not None:
                firsts = {int(sched.tables[s, 0]): r for s, r in sched.running.items()}
                for row, (first, s0, n) in enumerate(zip(np.asarray(bt)[:, 0],
                                                         np.asarray(start), np.asarray(vlen))):
                    r = firsts.get(int(first))
                    if r is not None and r.rid in self.capture and s0 + n == r.prompt_len:
                        self.capture[r.rid].append(out[0][row])
            return out

        def decode(p, pool, toks, pos, bt, active, trash):
            logits, pool = decode_fn(p, pool, toks, pos, bt, active, trash)
            if self.capture is not None:
                for s in np.flatnonzero(np.asarray(active)):
                    r = sched.running.get(int(s))
                    if r is not None and r.rid in self.capture:
                        self.capture[r.rid].append(logits[s])
            return logits, pool

        sched.plan, engine._chunk_fn, engine._decode_paged = planned, chunk, decode


@dataclasses.dataclass
class Served:
    """One request of the loop: its due and submit times and the time of
    each of its tokens (seconds from the schedule's start)."""

    plan: serve_gen.Planned
    req: object = None
    submit_s: float = math.nan
    times: List[float] = dataclasses.field(default_factory=list)
    refused: bool = False


class Loop:
    """The open loop: submits each request when it falls due, steps the
    engine while it has work, sleeps until the next due time while it has
    none, and stamps each new token with the host clock."""

    def __init__(self, engine, plans: List[serve_gen.Planned], last_due: float,
                 population: List[serve_gen.Planned] = ()):
        self.engine = engine
        self.served = ([Served(p) for p in population]
                       + [Served(p) for p in plans if p.due_s < last_due])
        self.next = 0
        self.live: Dict[int, Served] = {}
        self.steps = 0
        self.step_s = 0.0  # summed host time of the engine steps
        self.t0 = time.perf_counter()
        if population:
            self._settle(len(population))

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _settle(self, n: int) -> None:
        """Submit the first ``n`` requests (the population in flight) and
        step until none of them is queued or in prefill; the schedule's
        clock then starts."""
        self._arrivals(math.inf, upto=n)
        sched = self.engine.sched
        while sched.queue or any(r.in_prefill for r in sched.running.values()):
            self.engine.step()
            self._stamp(self.now())
        shift = self.now()
        self.t0 += shift
        for s in self.served[:n]:
            s.submit_s -= shift
            s.times = [x - shift for x in s.times]

    def _arrivals(self, now: float, upto: Optional[int] = None) -> None:
        from repro.resilience.recovery import ShedError
        from repro.serving.engine import Request

        upto = len(self.served) if upto is None else upto
        while self.next < upto and self.served[self.next].plan.due_s <= now:
            s = self.served[self.next]
            self.next += 1
            s.req = Request(rid=s.plan.idx, prompt=s.plan.prompt, max_new_tokens=s.plan.max_new)
            s.submit_s = self.now()
            try:
                self.engine.submit(s.req)
                self.live[s.plan.idx] = s
            except ShedError:
                s.refused = True

    def _stamp(self, t: float) -> None:
        for rid, s in list(self.live.items()):
            n = len(s.req.output)
            s.times += [t] * (n - len(s.times))
            if s.req.done:
                del self.live[rid]

    def run(self, until: float, stop: Optional[Callable[[], bool]] = None) -> None:
        """Until ``until`` seconds from the schedule's start, or until
        ``stop()`` holds."""
        while True:
            now = self.now()
            if now >= until or (stop is not None and stop()):
                return
            with harness.span("bench.serve.arrivals"):
                self._arrivals(now)
            if self.engine.sched.has_work:
                with harness.span("bench.serve.step"):
                    self.engine.step()
                self.steps += 1
                t = self.now()
                self.step_s += t - now
                self._stamp(t)
            else:
                nxt = self.served[self.next].plan.due_s if self.next < len(self.served) else until
                time.sleep(max(0.0, min(nxt, until) - now))

    def backlog(self) -> int:
        return len(self.engine.sched.queue) + len(self.engine.sched.running)


def window_stats(loop: Loop, w0: float, w1: float, t: Dict) -> Dict:
    """The window's numbers: TTFT of the requests due in [w0, w1), the
    token gaps that end in it, the share of those requests that met both
    limits (``ttft_limit_ms``, and ``itl_limit_ms`` on the mean gap of
    each), and how late the loop submitted."""
    due = [s for s in loop.served if w0 <= s.plan.due_s < w1]
    ttft = [(s.times[0] - s.plan.due_s) * 1e3 if s.times and not s.refused else math.inf
            for s in due]
    itl = [(b - a) * 1e3 for s in loop.served for a, b in zip(s.times, s.times[1:])
           if w0 <= b < w1]
    met = 0
    for s, f in zip(due, ttft):
        gaps = np.diff(s.times) * 1e3
        if f <= t["ttft_limit_ms"] and (gaps.size == 0 or gaps.mean() <= t["itl_limit_ms"]):
            met += 1
    late = [(s.submit_s - s.plan.due_s) * 1e3 for s in due if s.req is not None]
    failed = sum(1 for f in ttft if not math.isfinite(f))
    return {
        "ttft_ms": ttft, "itl_ms": itl, "requests": len(due), "failed": failed,
        "met_share": met / len(due) if due else math.nan,
        "ttft_mean_ms": float(np.mean(ttft)) if ttft else math.nan,
        "ttft_p50_ms": serve_gen.percentile(ttft, 50),
        "ttft_p90_ms": serve_gen.percentile(ttft, 90),
        "itl_p50_ms": serve_gen.percentile(itl, 50),
        "itl_p95_ms": serve_gen.percentile(itl, 95),
        "late_p50_ms": serve_gen.percentile(late, 50),
        "late_max_ms": max(late) if late else math.nan,
    }


def chunk_histogram(step_chunks: List[int]) -> Dict[int, int]:
    """How many engine steps ran 0, 1, 2, ... prefill chunks."""
    return {int(k): int(v) for k, v in zip(*np.unique(step_chunks, return_counts=True))}


def describe(stats: Dict) -> str:
    return (f"ttft_mean_ms {stats['ttft_mean_ms']!r} (median {stats['ttft_p50_ms']!r}, p90 "
            f"{stats['ttft_p90_ms']!r}, {len(stats['ttft_ms'])} requests, {stats['failed']} "
            f"failed: {[round(x, 1) for x in stats['ttft_ms']]}); itl_p95_ms "
            f"{stats['itl_p95_ms']!r} (median {stats['itl_p50_ms']!r}, {len(stats['itl_ms'])} "
            f"gaps); met both limits {stats['met_share']!r}; loop late median "
            f"{stats['late_p50_ms']!r} ms, max {stats['late_max_ms']!r} ms")


def run_checked(engine, calls: Calls, prompts: List[np.ndarray], steps: int) -> Dict[int, Dict]:
    """Serve the checked prompts alone through the engine, each to
    ``steps`` decode steps after its first token; returns per request its
    prompt, output and the logits rows the programs returned (the final
    prefill chunk's, then one per decode step), fetched to the host. A
    request whose rows are not one per token it got stops the run."""
    import jax

    from repro.serving.engine import Request

    reqs = [Request(rid=CHECKED_RID + i, prompt=p, max_new_tokens=steps + 1)
            for i, p in enumerate(prompts)]
    calls.capture = {r.rid: [] for r in reqs}
    for r in reqs:
        engine.submit(r)
    while engine.sched.has_work:
        engine.step()
    got = jax.device_get(calls.capture)
    calls.capture = None
    short = {r.rid: len(got[r.rid]) for r in reqs if len(got[r.rid]) != len(r.output)}
    if short:
        raise RuntimeError(f"the engine's programs returned logits rows for the checked "
                           f"requests that do not match their tokens: {short} rows, "
                           f"{len(reqs[0].output)} tokens each")
    return {r.rid: {"prompt": r.prompt, "output": list(r.output),
                    "logits": np.stack(got[r.rid])} for r in reqs}


def compare(seed: int, d: gen.Dims, std: float, checked: Dict[int, Dict],
            sampled: Dict[int, Dict], rounding: str = "f32") -> Dict[str, object]:
    """The reference over the checked and the sampled requests at once:
    the relative L2 gap of each checked logits row, and each sampled served
    token's gap below the reference's best. With ``rounding`` other than
    f32, the control: its logits take the program's place (checked rows)
    and its first choice the served token's (sampled positions)."""
    from benchmarks.chip import reference_serve as rs

    seqs, want = [], []
    for c in checked.values():
        P, n = len(c["prompt"]), len(c["logits"])
        seqs.append(np.concatenate([c["prompt"], np.asarray(c["output"][:n - 1], np.int32)]))
        want.append(range(P - 1, P - 1 + n))
    for s in sampled.values():
        P, n = len(s["prompt"]), len(s["output"])
        seqs.append(np.concatenate([s["prompt"], np.asarray(s["output"][:-1], np.int32)]))
        want.append(range(P - 1, P - 1 + n))
    ref = rs.logits(seed, d, std, seqs, want)
    nc = sum(len(c["logits"]) for c in checked.values())
    served = np.concatenate([np.asarray(s["output"], np.int32) for s in sampled.values()]
                            or [np.zeros(0, np.int32)])
    if rounding == "f32":
        prog = np.concatenate([c["logits"][:, :d.vocab] for c in checked.values()])
        gaps = rs.served_gaps(ref[nc:], served) if served.size else np.zeros(0)
    else:
        other = rs.logits(seed, d, std, seqs, want, rounding)
        prog = np.asarray(other[:nc])
        gaps = rs.first_choice_gaps(ref[nc:], other[nc:]) if served.size else np.zeros(0)
    l2 = rs.rel_l2(prog, np.asarray(ref[:nc]))
    return {"checked_logit_gap_mean": float(l2.mean()), "checked_logit_gap": float(l2.max()),
            **rs.gap_numbers(gaps)}
