"""Mixture-of-Experts layer: router + TokenDispatcher orchestration.

All dispatch/combine logic lives in the ``repro.core.dispatch`` subsystem;
this module routes tokens, picks the dispatcher, and applies the dense
residual. Three dispatchers (paper §3.2 tuning practice #2 + dropless):

* ``allgather`` — global-view pjit: tokens stay replicated over the EP
  axis, each expert shard gathers the (<= capacity) tokens routed to its
  local experts, combine is a scatter-add reduced over the EP axis.
* ``alltoall``  — shard_map formulation with explicit ``jax.lax.all_to_all``
  over the EP axis (preferred for small top-k, per the paper).
* ``sorted``    — MegaBlocks-style argsort token permutation into a flat
  (T*k, D) expert-sorted buffer + per-expert group_sizes; true dropless
  with no padded-capacity blow-up. Recommended with
  ``capacity_factor=None``.

Capacity (paper §2, padded dispatchers only): ``C = ceil(k *
tokens_per_group / E * CF)``; overflowing tokens are dropped from expert
compute and pass through on the residual stream. ``capacity_factor=None`` =
dropless (padded layout: C = tokens_per_group; sorted layout: exact).

Expert placement follows the FoldingPlan: 'expert' -> EP axis when the
expert count divides it, else expert hidden dim -> 'model' (expert-TP) —
MoE Parallel Folding on a fixed physical mesh.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, MoEConfig
from repro.core.dispatch import (
    capacity,
    dispatch_tables,
    expert_choice_tables,
    get_dispatcher,
)
from repro.core.router import route, router_decl
from repro.models.layers import mlp_apply, mlp_decl
from repro.sharding.rules import FoldingPlan, ParamDecl

# Backward-compat alias: tests/benchmarks import the table builder under its
# pre-subsystem name.
_dispatch_tables = dispatch_tables

__all__ = [
    "moe_decl",
    "moe_apply",
    "capacity",
    "dispatch_tables",
    "_dispatch_tables",
    "expert_choice_tables",
]


def moe_decl(cfg: ModelConfig, moe: MoEConfig) -> Dict[str, Any]:
    D = cfg.d_model
    F = moe.experts_ff(cfg.d_ff)
    E = moe.num_experts
    dt = jnp.bfloat16
    decls: Dict[str, Any] = {
        "router": router_decl(D, moe),
        "experts": {
            "w_gate": ParamDecl((E, D, F), ("expert", "embed", "expert_ff"), "fan_in", dt),
            "w_up": ParamDecl((E, D, F), ("expert", "embed", "expert_ff"), "fan_in", dt),
            "w_down": ParamDecl((E, F, D), ("expert", "expert_ff", "embed"), "fan_in", dt),
        },
    }
    if moe.dense_residual:
        decls["dense_residual"] = mlp_decl(D, cfg.d_ff, dt)
    return decls


def moe_apply(
    cfg: ModelConfig,
    moe: MoEConfig,
    plan: Optional[FoldingPlan],
    params,
    x: jax.Array,  # (B, S, D)
    rng: Optional[jax.Array] = None,
    train: bool = False,
    use_kernel: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    with jax.named_scope("moe_router"):
        if moe.router_type == "expert_choice":
            from repro.core.router import route_full

            gates, idx, aux = route_full(moe, params["router"], xf)
        else:
            gates, idx, aux = route(moe, params["router"], xf, rng, train)

    dispatcher = get_dispatcher(cfg, moe, plan, T, B)
    out = dispatcher.apply(params["experts"], xf, gates, idx, use_kernel)

    out = out.reshape(B, S, D).astype(x.dtype)
    if moe.dense_residual:
        out = out + mlp_apply(params["dense_residual"], x)
    return out, aux
