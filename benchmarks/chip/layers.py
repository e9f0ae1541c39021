"""Device self time of the train step by layer, read from the layer names
that the program writes into each op's op-name path (``jax.named_scope``).

The program names these layers (``models/``, ``core/``, ``train/trainer.py``):

* ``embed``: the token lookup and its scatter-add gradient;
* ``layer_scan``: the layer scan's own slicing of the stacked weights and
  stacking of outputs, and the block's glue outside the scopes below;
* ``norm``, ``attention``: the block's LayerNorms and its mixer (QKV and
  out projections, rope, the flash kernel);
* ``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``:
  routing, the gather into the expert-sorted buffer, the expert FFN
  (grouped-GEMM kernels and their glue), the gather back and scatter-add;
* ``head_loss``: the final norm, the LM head and the cross entropy;
* ``adamw``, ``grad_norm``, ``anomaly_guard``: the update, the trainer's
  second gradient norm, and the guard's whole-state select-back.

An op's layer is the innermost path segment that names one of them, read
with the transform wrappers (``jvp(``, ``transpose(``, ``)``) stripped; an
op of the step with no such segment is ``unscoped``. Each op's phase is
``trace.phase``, so a phase's layers and ``unscoped`` sum to that phase.

:func:`layer_ms` reduces the traced window once per run (memoised on the
trace object, so all readers share one walk) and prints each phase's layers
with their three largest op families. Where the program names its layers,
a layer that the configuration has and that reads no op stops the run, as
``kinds/train.py:read_trace`` does for the phases: a renamed scope. A
program that names none (before the scopes were written) reads nothing.
"""
from __future__ import annotations

import re
import sys
import time
from collections import defaultdict
from typing import Any, Dict, Optional

from . import trace

LAYERS = ("embed", "layer_scan", "norm", "attention", "moe_router", "moe_dispatch",
          "moe_experts", "moe_combine", "head_loss", "adamw", "grad_norm", "anomaly_guard")
MOE_LAYERS = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
UNSCOPED = "unscoped"
_WRAPPERS = re.compile(r"^(?:transpose\()?(?:jvp\()?|\)+$")


def layer_of(path: str) -> str:
    """The innermost segment of an op-name path that names a layer, or
    ``unscoped``. A TPU trace ends the path with ``:``."""
    for seg in reversed(path.rstrip(":").split("/")):
        name = _WRAPPERS.sub("", seg)
        if name in LAYERS:
            return name
    return UNSCOPED


def layer_family_ns(tr: trace.Trace, lo: float, hi: float
                    ) -> Dict[str, Dict[str, Dict[str, float]]]:
    """Self time within [lo, hi] of the ops of the step's module, by phase
    (``trace.PHASES``), layer and op family, averaged over the devices that
    ran anything."""
    by: Dict[str, Dict[str, Dict[str, float]]] = {
        p: defaultdict(lambda: defaultdict(float)) for p in trace.PHASES}
    n = max(1, sum(1 for ops in tr.devices.values() if ops))
    layers: Dict[str, str] = {}  # op-name path -> layer, one parse per path
    for ops in tr.devices.values():
        for o, t in trace.self_ns(ops, lo, hi):
            p = trace.phase(o)
            if p == "other":
                continue
            if o.scope not in layers:
                layers[o.scope] = layer_of(o.scope)
            by[p][layers[o.scope]][o.family] += t / n
    return by


def expected(ctx: Dict[str, Any]):
    """The layers that the cell's step must show: attention always, the
    anomaly guard because ``Trainer.run`` arms it every step, the MoE
    layers where the configuration has experts."""
    need = ["attention", "anomaly_guard"]
    if ctx["dims"].num_experts > 1:
        need += MOE_LAYERS
    return need


def layer_ms(ctx: Dict[str, Any]) -> Optional[Dict[str, Dict[str, float]]]:
    """Milliseconds per step by phase and layer (``unscoped`` included) of
    the traced window, or None where no op of the step names a layer."""
    tr = ctx["trace"]
    key = (ctx["lo"], ctx["hi"], ctx["steps"])
    memo = tr.__dict__.setdefault("_layer_ms", {})
    if key not in memo:
        memo[key] = _reduce(ctx)
    return memo[key]


def total_ms(ctx: Dict[str, Any], layer: str) -> Optional[float]:
    """One layer's milliseconds per step over all phases; None where the
    trace names no layer or this one read nothing."""
    if ctx["kind"] != "train" or not ctx["steps"]:
        return None
    ms = layer_ms(ctx)
    if ms is None:
        return None
    return sum(by.get(layer, 0.0) for by in ms.values()) or None


def _reduce(ctx: Dict[str, Any]) -> Optional[Dict[str, Dict[str, float]]]:
    t0 = time.perf_counter()
    fams = layer_family_ns(ctx["trace"], ctx["lo"], ctx["hi"])
    per = 1e-6 / ctx["steps"]
    ms = {p: {layer: sum(f.values()) * per for layer, f in by.items()}
          for p, by in fams.items()}
    named = {layer for by in ms.values() for layer in by if layer != UNSCOPED}
    if not named:
        print("layers: no op of the step names a layer; nothing read", file=sys.stderr)
        return None
    missing = [layer for layer in expected(ctx) if layer not in named]
    if missing:
        raise RuntimeError(
            f"layers: no device op of the window under {trace.STEP_MODULE!r} is in "
            f"layer {', '.join(missing)}; a scope of the program was renamed or lost")
    for p, by in fams.items():
        rows = []
        for layer in sorted(by, key=lambda name: -ms[p][name]):
            top = sorted(by[layer].items(), key=lambda kv: -kv[1])
            top = top[:5 if layer == UNSCOPED else 3]
            rows.append(f"{layer} {ms[p][layer]!r} ("
                        + ", ".join(f"{fam} {ns * per!r}" for fam, ns in top) + ")")
        print(f"layers: {p} ms per step: " + "; ".join(rows), file=sys.stderr)
    print(f"layers: reduced in {time.perf_counter() - t0!r} s", file=sys.stderr)
    return ms
