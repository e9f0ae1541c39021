"""Requests and diverged experts of a serving run, made from ``--seed``.

Like ``gen.py``, this module is the benchmark's own and imports nothing of
the program: the serving kind (``kinds/serve.py``) and the serving
reference (``reference_serve.py``) read the same values from here.

* Traffic: the mix fixes the work, as a replayed trace does: the prompt
  lengths, answer lengths and gaps between arrivals are the quantiles of
  its log-normal and exponential laws, in an order drawn once from its
  ``schedule_seed``, for the pre-roll, the window and the population in
  flight when the arrivals start. The run's seed draws the token ids,
  Zipf-skewed over the served vocabulary (``gen.zipf_tokens``), and the
  weights, so routing, expert load and the sampled answers differ from
  seed to seed. Each block holds the laws' mid-quantiles, so every run
  serves the mix's distribution itself, in one fixed order: a window of
  some ten to sixteen requests cannot average over orders, and with the
  order drawn from the run's seed six seeds' tails spread by 18-25% while
  two runs of one seed agreed within 0-12% (PERF.md §2).
* Diverged experts: a served upcycled model has trained on, so its
  experts differ. Expert ``e`` of layer ``l`` is the dense FFN plus
  ``N(0, delta_std)``, a pure function of (seed, name, layer, expert).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import gen

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
POPULATION_RID = 1 << 29  # the population's request ids, above any scheduled one


@dataclasses.dataclass
class Planned:
    """One request of the schedule: due ``due_s`` after the schedule's start."""

    idx: int
    due_s: float
    prompt: np.ndarray  # (S,) int32
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def lognormal_lengths(law: Dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a log-normal law with ``median`` and
    ``sigma``, rounded and clipped to [``min``, ``max``], in rising order."""
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(n)])
    x = np.round(law["median"] * np.exp(law["sigma"] * z))
    return np.clip(x, law["min"], law["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of the exponential law of Poisson arrivals
    at ``rate`` per second, in rising order."""
    return -np.log1p(-_quantiles(n)) / rate


def _block(t: Dict, name: str, n: int):
    """``n`` prompt and answer lengths, the mid-quantiles of the mix's laws,
    each in an order of its own drawn from the mix's ``schedule_seed``."""
    order = lambda x, what: x[gen.host_rng(t["schedule_seed"], f"{name}.{what}").permutation(n)]
    return (order(lognormal_lengths(t["prompt"], n), "prompt_len"),
            order(lognormal_lengths(t["output"], n), "output_len"), order)


def _ids(t: Dict, seed: int, name: str, vocab: int):
    rng = gen.host_rng(seed, f"{name}.ids")
    perm = gen.host_rng(seed, "perm").permutation(vocab)
    probs = gen.zipf_probs(vocab, t["zipf_a"])
    return lambda n: gen.zipf_tokens(rng, probs, perm, (int(n),))


def schedule(t: Dict, seed: int, seconds: float, vocab: int) -> List[Planned]:
    """Open-loop arrivals in two blocks, the pre-roll and the window, each
    of ``rate_per_s`` x its length requests whose gaps (the exponential
    law's mid-quantiles) are scaled to span it exactly. Lengths and gaps
    come in the order of the mix's ``schedule_seed``, the token ids from
    ``seed``. Sorted by due time."""
    out: List[Planned] = []
    start = 0.0
    for name, span in (("preroll", t["preroll_s"]), ("window", seconds)):
        n = max(1, int(round(t["rate_per_s"] * span)))
        plens, olens, order = _block(t, name, n)
        gaps = order(exponential_gaps(t["rate_per_s"], n), "gaps")
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (span / gaps.sum())
        ids = _ids(t, seed, name, vocab)
        first = len(out)
        out += [Planned(first + i, float(due[i]), ids(plens[i]), int(olens[i])) for i in range(n)]
        start += span
    return out


def length_biased(law: Dict, n: int, grid: int = 4096) -> np.ndarray:
    """``n`` lengths of the law weighted by length, in rising order: the
    answer lengths of the requests a server holds in flight at one moment
    (a request stays about one engine step per answer token, so a long one
    is met in proportion to its length). The mid-quantiles of the weighted
    law over ``grid`` mid-quantiles of ``law``."""
    x = lognormal_lengths(law, grid)
    cdf = np.cumsum(x) / x.sum()
    return x[np.minimum(np.searchsorted(cdf, _quantiles(n)), grid - 1)]


def population(t: Dict, seed: int, vocab: int) -> List[Planned]:
    """The requests in flight when the arrivals start, as a server in
    steady state at ``rate_per_s`` holds them (Little's law):
    ``rate_per_s`` x ``residence_s`` requests, ``residence_s`` being the
    mean time a request stays in flight at that rate as ``sweep.py``
    measured it on the chip (PERF.md §2). Prompt lengths follow the law,
    answer lengths the law weighted by length (``length_biased``), the
    k-th having served (k + 0.5) / n of its answer (in the
    ``schedule_seed``'s order); each is its prompt extended by the tokens
    it has served (ids from ``seed``), with the rest of its answer to go."""
    n = max(1, int(round(t["rate_per_s"] * t["residence_s"])))
    plens, _, order = _block(t, "population", n)
    olens = order(length_biased(t["output"], n), "output_len")
    frac = order(_quantiles(n), "served")
    ids = _ids(t, seed, "population", vocab)
    out = []
    for i in range(n):
        served = min(int(frac[i] * olens[i]), int(olens[i]) - 1)
        out.append(Planned(POPULATION_RID + i, -math.inf, ids(plens[i] + served),
                           int(olens[i]) - served))
    return out


def steps_per_request(t: Dict, prefill_chunk: int, n: int = 4096) -> float:
    """Mean engine steps a request of the mix stays in flight: one per
    prefill chunk of its prompt, then one per answer token after the first
    (which the final chunk gives)."""
    chunks = np.ceil(lognormal_lengths(t["prompt"], n) / prefill_chunk)
    return float(chunks.mean() + lognormal_lengths(t["output"], n).mean() - 1)


def checked_prompts(t: Dict, seed: int, vocab: int) -> List[np.ndarray]:
    """The prompts of the requests checked logit by logit before the
    window: ``count`` lengths spread evenly over [``prompt_min``,
    ``prompt_max``] (several prefill chunks, page boundaries crossed)."""
    c = t["checked"]
    lens = np.linspace(c["prompt_min"], c["prompt_max"], c["count"]).round().astype(int)
    rng = gen.host_rng(seed, "checked_ids")
    perm = gen.host_rng(seed, "perm").permutation(vocab)
    probs = gen.zipf_probs(vocab, t["zipf_a"])
    return [gen.zipf_tokens(rng, probs, perm, (int(n),)) for n in lens]


def sample_served(candidates: List[int], lengths: List[int], k: int, seed: int) -> List[int]:
    """``k`` of the requests ``candidates`` (ids, with their served token
    counts ``lengths``): the one with the most served tokens and ``k - 1``
    others drawn from the seed."""
    if not candidates:
        return []
    longest = candidates[int(np.argmax(lengths))]
    rest = [c for c in candidates if c != longest]
    pick = gen.host_rng(seed, "sample").permutation(len(rest))[: k - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def expert_delta(key: jax.Array, d: gen.Dims, layer, expert, name: str,
                 std: float) -> jax.Array:
    """How expert ``expert`` of layer ``layer`` departs from the dense FFN
    in leaf ``name`` (float32, the leaf's (D, F) or (F, D) shape)."""
    shape = (d.d_ff, d.d_model) if name == "w_down" else (d.d_model, d.d_ff)
    k = gen._key(key, "delta." + name, layer, expert)
    return jax.random.normal(k, shape, jnp.float32) * std


def diverged(dense: jax.Array, key: jax.Array, d: gen.Dims, layer, expert, name: str,
             std: float) -> jax.Array:
    """An expert's leaf as served: the dense leaf (its bfloat16 value) plus
    the expert's delta, rounded to bfloat16, returned as float32."""
    return gen.bf16(dense + expert_delta(key, d, layer, expert, name, std))


def delta_std(config: Dict) -> float:
    """``run.expert_delta_std`` x the published initializer range."""
    return config["run"]["expert_delta_std"] * gen.INIT_STD


def percentile(values, q: float) -> float:
    """The ``q``-th percentile, linear between order statistics; a missed
    value is inf and counts (the next order statistic up is taken where
    one is inf); nan for no values."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return math.nan
    return float(np.percentile(v, q, method="linear" if np.isfinite(v).all() else "higher"))
