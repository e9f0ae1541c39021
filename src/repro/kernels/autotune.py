"""Roofline-driven Pallas tile autotuner with a persistent cache.

The static ``_pick`` heuristics in ``expert_gemm`` / ``flash_attention`` /
``paged_attention`` choose one tile size per dimension from a fixed default
(flash attention's from the shape, ``flash_tiles``).
That is robust but leaves performance on the table when the problem shape
makes a different lane split cheaper (e.g. small-D experts where a wider F
tile amortizes weight re-reads, or short KV pages where a sub-page block
fits VMEM better). This module searches the candidate tile space per
problem key and scores each candidate with the ``roofline/analysis.py``
hardware model:

* **measured** scoring: where a caller can provide a ``measure(blocks)``
  wall-time callable (a real accelerator backend), the tuner uses median
  wall time directly;
* **modeled** scoring (the default, and the only option on CPU/interpret
  runs): per-candidate HBM bytes and FLOPs from an analytic traffic model
  of the kernel's grid, turned into seconds via the active
  :func:`repro.roofline.analysis.hw_profile` (``max(flops/peak,
  bytes/bw)`` plus a per-grid-step launch overhead), with candidates whose
  working set exceeds the profile's VMEM budget filtered out.

Winners persist in a versioned JSON cache so tuning cost is paid once per
machine: ``~/.cache/repro_autotune.json`` (override with
``REPRO_AUTOTUNE_CACHE``), seeded from the repo-committed
``autotune_defaults.json`` next to this file. Cache entries whose version
does not match :data:`CACHE_VERSION` are discarded; every winner — fresh or
cached — is re-validated for lane alignment (last-dim tiles must divide the
dim into multiple-of-128 lanes, sublane tiles multiple-of-8) and dropped if
a stale/poisoned entry fails, falling back to a fresh search.

Tuning is **opt-in**: resolution order is ``--autotune`` CLI flag ->
``REPRO_AUTOTUNE=1`` env -> off. When off, :func:`get_blocks` returns the
caller's fallback (the existing static heuristic) untouched, so default
behavior is byte-identical to the pre-autotuner code path. ``_pick`` also
remains the in-kernel fallback on any cache miss with tuning disabled.

The module is importable without jax (scoring is pure arithmetic); only
the alignment validator lazily imports ``_pick``'s host module.
"""
from __future__ import annotations

import json
import os
import tempfile
from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

CACHE_VERSION = 1

# Per-grid-step launch/bookkeeping overhead (seconds) in the modeled score:
# keeps the model from preferring degenerate many-tiny-tile grids that the
# pure bandwidth term would rate as free.
STEP_OVERHEAD_S = 5e-7

# Candidate tile sizes per tunable dim. Lane dims (last axis) must split
# into multiples of 128; sublane dims (rows, sequence, page tokens) into
# multiples of 8 — the small end exists for sub-page KV tiles.
LANE_CANDIDATES = (128, 256, 512, 1024)
SUBLANE_CANDIDATES = (8, 16, 32, 64, 128, 256, 512)

_stats = {"hits": 0, "misses": 0}
_memo: Dict[Tuple[str, str], Tuple[int, ...]] = {}
_cache_loaded: Optional[dict] = None


def reset() -> None:
    """Test hook: drop the in-memory memo/cache and zero the hit counters
    (the on-disk cache file is left alone)."""
    global _cache_loaded
    _memo.clear()
    _cache_loaded = None
    _stats["hits"] = 0
    _stats["misses"] = 0


def stats() -> Dict[str, int]:
    return dict(_stats)


def enabled() -> bool:
    """Autotuning is opt-in: off unless ``REPRO_AUTOTUNE`` is a truthy env
    value (the ``--autotune`` CLI flags set it). Read per call."""
    return os.environ.get("REPRO_AUTOTUNE", "").lower() in ("1", "true", "on")


def cache_path() -> str:
    p = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if p:
        return p
    return os.path.join(
        os.path.expanduser(os.environ.get("XDG_CACHE_HOME", "~/.cache")),
        "repro_autotune.json",
    )


def _defaults_path() -> str:
    return os.path.join(os.path.dirname(__file__), "autotune_defaults.json")


def make_key(
    kernel: str,
    *,
    E: int = 0,
    k: int = 0,
    D: int = 0,
    F: int = 0,
    page_size: int = 0,
    itemsize: int = 2,
    extra: str = "",
) -> str:
    """Canonical cache key: one winner per (kernel, problem dims, element
    width). ``extra`` carries kernel-specific dims (e.g. flash-attention
    sequence lengths)."""
    key = f"{kernel}|E{E}|k{k}|D{D}|F{F}|ps{page_size}|it{itemsize}"
    return f"{key}|{extra}" if extra else key


# ---------------------------------------------------------------------------
# Cache I/O
# ---------------------------------------------------------------------------


def _load_file(path: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(data, dict) or data.get("version") != CACHE_VERSION:
        return {}  # version mismatch -> invalidate wholesale
    profiles = data.get("profiles")
    return profiles if isinstance(profiles, dict) else {}


def _load_cache() -> dict:
    """Merged profiles dict {profile: {key: entry}}: the user cache wins
    over the repo-committed defaults."""
    global _cache_loaded
    if _cache_loaded is None:
        merged: dict = {}
        for path in (_defaults_path(), cache_path()):
            for prof, entries in _load_file(path).items():
                merged.setdefault(prof, {}).update(entries)
        _cache_loaded = merged
    return _cache_loaded


def _persist(profile: str, key: str, entry: dict) -> None:
    """Atomic read-modify-write of the user cache (tmp file + rename).
    Best-effort: an unwritable cache dir degrades to in-memory tuning."""
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        on_disk = {}
        try:
            with open(path) as f:
                data = json.load(f)
            if isinstance(data, dict) and data.get("version") == CACHE_VERSION:
                on_disk = data.get("profiles", {})
        except (OSError, ValueError):
            pass
        on_disk.setdefault(profile, {})[key] = entry
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".")
        with os.fdopen(fd, "w") as f:
            json.dump({"version": CACHE_VERSION, "profiles": on_disk}, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


# ---------------------------------------------------------------------------
# Candidate generation + validation
# ---------------------------------------------------------------------------


def _legal_split(block: int, dim: int, align: int) -> bool:
    """``_pick``'s legality contract: the tile must divide the dim; lane
    dims (align >= 128) additionally require a multiple-of-128 tile unless
    the tile spans the whole (compiler-padded) dim; sublane dims accept any
    divisor (the compiler pads sublanes)."""
    if block <= 0 or block > dim or dim % block:
        return False
    if align >= 128:
        return block % align == 0 or block == dim
    return True


def validate_blocks(
    blocks: Sequence[int], dims: Sequence[int], aligns: Sequence[int]
) -> bool:
    """Lane-alignment check applied to *every* winner before use — fresh
    search results are asserted, cached entries failing it are treated as
    poisoned and dropped (version skew, hand-edited cache, different
    alignment rules)."""
    if len(blocks) != len(dims):
        return False
    for b, d, a in zip(blocks, dims, aligns):
        if not isinstance(b, int):
            return False
        if not _legal_split(b, d, a):
            return False
    return True


def candidates(
    dims: Sequence[int], aligns: Sequence[int],
    fixed: Sequence[Optional[int]] = (),
) -> Iterable[Tuple[int, ...]]:
    """Cartesian product of legal tile candidates per dim (pool entries
    preferring align-multiples; a whole-dim tile is always offered).
    ``fixed`` pins a dim to a single structural value (e.g. the sorted
    dispatcher's row_block, which is part of the buffer layout and not
    tunable)."""
    fixed = tuple(fixed) + (None,) * (len(dims) - len(fixed))
    per_dim = []
    for d, a, fx in zip(dims, aligns, fixed):
        if fx is not None:
            per_dim.append([fx])
            continue
        pool = LANE_CANDIDATES if a >= 128 else SUBLANE_CANDIDATES
        opts = {min(c, d) for c in pool}
        opts.add(d)  # whole-dim tile: always legal
        per_dim.append(sorted(o for o in opts if _legal_split(o, d, a)))
    out = [()]
    for opts in per_dim:
        out = [prev + (o,) for prev in out for o in opts]
    return out


# ---------------------------------------------------------------------------
# Scoring
# ---------------------------------------------------------------------------


def modeled_seconds(
    flops: float, bytes_hbm: float, steps: float, hw: Optional[dict] = None
) -> float:
    """Roofline score of one candidate: compute/memory lower bound plus a
    per-grid-step overhead term."""
    if hw is None:
        from repro.roofline.analysis import hw_profile

        hw = hw_profile()
    return max(flops / hw["peak_flops"], bytes_hbm / hw["hbm_bw"]) + steps * STEP_OVERHEAD_S


def _vmem_ok(vmem_bytes: float, hw: dict) -> bool:
    return vmem_bytes <= 0.7 * hw["vmem_bytes"]


def search(
    cands: Iterable[Tuple[int, ...]],
    cost: Callable[[Tuple[int, ...]], Dict[str, float]],
    measure: Optional[Callable[[Tuple[int, ...]], float]] = None,
    hw: Optional[dict] = None,
) -> Tuple[Tuple[int, ...], float, str]:
    """Pick the best candidate. ``cost(blocks)`` returns the analytic
    ``{"flops", "bytes", "steps", "vmem_bytes"}`` model of the kernel at
    that tiling; ``measure(blocks)`` (optional) returns measured wall
    seconds and takes precedence. Deterministic: ties break toward the
    lexicographically-smallest block tuple. Returns
    (blocks, score_s, source)."""
    if hw is None:
        from repro.roofline.analysis import hw_profile

        hw = hw_profile()
    best: Optional[Tuple[float, Tuple[int, ...]]] = None
    source = "measured" if measure is not None else "modeled"
    for blocks in sorted(cands):
        c = cost(blocks)
        if not _vmem_ok(c.get("vmem_bytes", 0.0), hw):
            continue
        if measure is not None:
            s = measure(blocks)
        else:
            s = modeled_seconds(c["flops"], c["bytes"], c.get("steps", 0.0), hw)
        if best is None or s < best[0]:
            best = (s, blocks)
    if best is None:
        raise ValueError("no candidate fits the VMEM budget")
    return best[1], best[0], source


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def get_blocks(
    kernel: str,
    key: str,
    fallback: Tuple[int, ...],
    dims: Sequence[int],
    aligns: Sequence[int],
    cost: Callable[[Tuple[int, ...]], Dict[str, float]],
    fixed: Sequence[Optional[int]] = (),
    measure: Optional[Callable[[Tuple[int, ...]], float]] = None,
) -> Tuple[int, ...]:
    """Resolve the tile config for one kernel call site.

    With tuning disabled (the default) this returns ``fallback`` — the
    static ``_pick`` heuristic's choice — unchanged. With tuning enabled it
    consults the in-memory memo, then the persistent cache (validating
    lane alignment and dropping poisoned entries), then runs the candidate
    search, persists the winner, and returns it. Shapes-only: safe to call
    under ``jit`` tracing since every input is static.
    """
    if not enabled():
        return tuple(fallback)
    from repro.roofline.analysis import hw_profile

    profile = os.environ.get("REPRO_HW_PROFILE") or "v5e"
    memo_key = (profile, key)
    if memo_key in _memo:
        _stats["hits"] += 1
        return _memo[memo_key]

    cached = _load_cache().get(profile, {}).get(key)
    if cached is not None:
        blocks = tuple(cached.get("blocks", ()))
        if validate_blocks(blocks, dims, aligns):
            _stats["hits"] += 1
            _memo[memo_key] = blocks
            return blocks
        # poisoned/stale entry: fall through to a fresh search

    _stats["misses"] += 1
    hw = hw_profile(profile)
    try:
        blocks, score, source = search(
            candidates(dims, aligns, fixed), cost, measure=measure, hw=hw
        )
    except ValueError:
        return tuple(fallback)
    assert validate_blocks(blocks, dims, aligns), (kernel, key, blocks)
    _memo[memo_key] = blocks
    _persist(profile, key, {
        "v": CACHE_VERSION,
        "blocks": list(blocks),
        "score_s": score,
        "source": source,
        "kernel": kernel,
    })
    return blocks
