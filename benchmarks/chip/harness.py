"""One run of one cell: the runner of the cell's traffic kind, the
per-layer readers, and the result line.

The mix's ``kind`` names the module ``kinds/<kind>.py``; its ``run`` does
set-up, the measured window and the check against the reference, and
returns a :class:`Run`: the end-to-end numbers, the compared numbers with
their limits, and for a traced run the context that the per-layer metric
readers read. A kind may also define ``read_trace(ctx, device)``, which
reduces the loaded trace once to what its readers share and returns the
context they read. What the kinds share is here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import shutil
import sys
import tempfile
import time
import types
from typing import Any, Dict, List, Tuple

import numpy as np

from . import bench, flops, trace


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    e2e: Dict[str, float]
    checks: Dict[str, Tuple[float, float]]  # name -> (number, limit)
    attempted: int
    failed: int
    ctx: Dict[str, Any] = dataclasses.field(default_factory=dict)


def devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    flops.peaks(devs[0].device_kind)  # an unknown kind is an error
    return devs[:chips]


def enable_cache() -> str:
    """The program's persistent compilation cache
    (``JAX_COMPILATION_CACHE_DIR`` where it is set, else
    ``<checkout>/.jax_cache``), with every program cached however fast it
    compiled, so that a second run of a cell compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_memory(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)


@contextlib.contextmanager
def traced(on: bool):
    """Profile the block when ``on``; yields a holder whose ``path`` is the
    ``.xplane.pb`` once the block has closed. The trace is written under
    TMPDIR and removed by :func:`reduce_trace`."""
    holder = types.SimpleNamespace(path=None, dir=None)
    if not on:
        yield holder
        return
    import glob

    import jax

    holder.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(holder.dir, profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
        files = glob.glob(f"{holder.dir}/**/*.xplane.pb", recursive=True)
        holder.path = files[0] if files else None


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def free(tree) -> None:
    """Delete every device buffer of ``tree`` now, not when Python gets to
    it: the reference needs the memory."""
    import jax

    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "delete"):
            leaf.delete()


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep: List[str]) -> Tuple[float, str]:
    """The largest |prog - ref| over the kept leaves, each against the
    larger of its reference norm and the median leaf's."""
    med = float(np.median([ref[k] for k in keep]))
    worst, at = 0.0, ""
    for k in keep:
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not math.isfinite(g):
            return float("inf"), k
        if g > worst:
            worst, at = g, k
    return worst, at


def kept_leaves(ref_grads: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is more than a thousandth of the
    median leaf's: the others move under Adam by round-off alone."""
    med = float(np.median(list(ref_grads.values())))
    return sorted(k for k, v in ref_grads.items() if v > 1e-3 * med)


# ---------------------------------------------------------------------------
# one run: the runner, the per-layer readers, the result line
# ---------------------------------------------------------------------------


def reduce_trace(ctx: Dict[str, Any], chips: int, device_kind: str) -> Dict[str, Any]:
    """Load the traced window's trace into ``ctx`` for the readers: the
    trace, the window on its clock, the chip's peaks."""
    tr = trace.load(ctx["trace_path"])
    shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    win = trace.span_window(tr, "bench.window") or tr.window()
    return {**ctx, "trace": tr, "lo": win[0], "hi": win[1], "chips": chips,
            "peaks": flops.peaks(device_kind)}


def execute(cell: bench.Cell, seed: int, seconds: float, trace_on: bool,
            t_start: float, devs) -> Dict[str, Any]:
    kind = bench.traffic_kind(cell.traffic["kind"], cell.here)
    run = kind.run(cell, seed, seconds, trace_on, t_start, devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.ctx["memory_peak_bytes"]}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics: Dict[str, Dict[str, Any]] = {}
    out: Dict[str, Any] = {}
    if trace_on:
        t0 = time.perf_counter()
        size = os.path.getsize(run.ctx["trace_path"])
        ctx = reduce_trace(run.ctx, len(devs), devs[0].device_kind)
        lo, hi = ctx["lo"], ctx["hi"]
        device["busy_s"] = trace.mean_busy_ns(ctx["trace"], lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        if hasattr(kind, "read_trace"):
            ctx = kind.read_trace(ctx, device)
        for m in cell.per_layer:
            value = bench.metric_reader(m["name"], cell.here).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace.top_ops(ctx["trace"], lo, hi)],
            "idle_gaps": [[n, s] for n, s in trace.attribute_gaps(ctx["trace"], lo, hi)],
        }
        print(f"trace: {size} bytes reduced in {time.perf_counter() - t0!r} s", file=sys.stderr)
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": run.e2e[m["name"]], "unit": units[m["name"]]}
    correct = all(math.isfinite(v) and v <= lim for v, lim in run.checks.values())
    return {
        "correct": bool(correct), "attempted": run.attempted, "failed": run.failed,
        "metrics": metrics, "device": device, **out,
        "checks": {k: {"value": v, "limit": lim} for k, (v, lim) in run.checks.items()},
    }
