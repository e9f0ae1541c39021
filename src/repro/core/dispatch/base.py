"""TokenDispatcher subsystem: the common interface plus the shared
dispatch bookkeeping (capacity, table building, group splitting).

A dispatcher moves routed tokens between the token-major layout the model
computes in and an expert-major layout the expert kernels consume:

* ``dispatch(x, idx, gates)`` -> ``(expert-major tokens, DispatchState)``.
  The state carries the :class:`DispatchLayout` descriptor the kernel layer
  consumes (dense padded ``(E, C, D)`` vs. flat expert-sorted ``(N, D)`` +
  ``group_sizes``) plus the residual arrays combine needs to reverse the
  permutation.
* ``combine(ye, state)``      -> token-major ``(T, D)`` output with the
  gate weighting applied.

Dispatchers hold NO mutable per-invocation state: all per-call values flow
through the returned :class:`DispatchState`, so one instance is re-entrant
under ``jax.grad`` / ``jax.vmap`` / nested tracing (dispatch twice, combine
in any order).

Concrete dispatchers live in sibling modules: ``allgather`` (global-view
pjit), ``alltoall`` (shard_map + lax.all_to_all over the EP axis), and
``sorted`` (argsort token permutation; true dropless, no padded capacity).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.rules import FoldingPlan


# ---------------------------------------------------------------------------
# Layout descriptor consumed by the kernel layer
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DispatchLayout:
    """Describes the expert-major buffer a dispatcher produced.

    * ``kind="padded"``: dense ``(..., E, C, D)`` buffer, ``capacity`` slots
      per expert; slots past the routed count hold garbage and are masked by
      the gate weights at combine.
    * ``kind="sorted"``: flat ``(N, D)`` expert-sorted buffer with
      ``group_sizes`` (E,) valid rows per expert. ``row_block`` is the row
      alignment of each expert's region (1 = compact; the Pallas grouped
      GEMM requires its row-tile size so every tile maps to one expert).
    """

    kind: str
    num_experts: int
    capacity: Optional[int] = None
    group_sizes: Optional[jax.Array] = None
    row_block: int = 1


@dataclasses.dataclass
class DispatchState:
    """Per-invocation dispatch residuals, returned by ``dispatch`` and
    passed back to ``combine``. ``layout`` describes the expert-major
    buffer for the kernel layer; ``residuals`` holds the arrays the
    concrete dispatcher needs to reverse its permutation (selection tables,
    argsort destinations, gate weights, ...); ``static`` holds hashable
    shape/geometry metadata (token counts, shard factors, axis names).
    Keeping these out of the dispatcher instance makes dispatch/combine
    pure functions of their inputs — re-entrant under jax.grad, jax.vmap,
    and nested traces. Both this class and :class:`DispatchLayout` are
    registered pytrees (arrays are leaves, everything else aux data), so
    the state may legally cross jit/vmap/scan boundaries."""

    layout: DispatchLayout
    residuals: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    static: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _layout_flatten(l: DispatchLayout):
    return (l.group_sizes,), (l.kind, l.num_experts, l.capacity, l.row_block)


def _layout_unflatten(aux, children):
    kind, num_experts, cap, row_block = aux
    return DispatchLayout(
        kind, num_experts, capacity=cap, group_sizes=children[0], row_block=row_block
    )


jax.tree_util.register_pytree_node(DispatchLayout, _layout_flatten, _layout_unflatten)


def _state_flatten(s: DispatchState):
    keys = tuple(sorted(s.residuals))
    children = (s.layout,) + tuple(s.residuals[k] for k in keys)
    return children, (keys, tuple(sorted(s.static.items())))


def _state_unflatten(aux, children):
    keys, static_items = aux
    return DispatchState(children[0], dict(zip(keys, children[1:])), dict(static_items))


jax.tree_util.register_pytree_node(DispatchState, _state_flatten, _state_unflatten)


# ---------------------------------------------------------------------------
# Shared dispatch bookkeeping
# ---------------------------------------------------------------------------


def capacity(moe, tokens_per_group: int) -> int:
    """Paper §2: ``C = ceil(k * tokens_per_group / E * CF)``. ``CF=None`` =
    dropless under the padded layout (worst case: one expert takes all)."""
    if moe.capacity_factor is None:
        return tokens_per_group
    c = math.ceil(moe.top_k * tokens_per_group / moe.num_experts * moe.capacity_factor)
    # an expert can receive each token at most once -> capacity <= T
    return max(min(int(c), tokens_per_group), 1)


def num_groups(plan: Optional[FoldingPlan], total_tokens: int, batch: int) -> int:
    """Tokens are dispatched in groups (GShard-style) so capacity and the
    dispatch working set stay per-data-shard. Groups = batch shards."""
    if plan is None:
        return 1
    g = int(np.prod([plan.mesh.shape[a] for a in plan.batch_axes])) or 1
    while g > 1 and (batch % g != 0 or total_tokens % g != 0):
        g -= 1
    return max(g, 1)


def expert_choice_tables(
    probs_full: jax.Array, E: int, C: int
) -> Tuple[jax.Array, jax.Array]:
    """Expert-Choice routing (Zhou et al., cited by the paper as the
    alternative to Top-k): each EXPERT picks its top-C tokens by router
    probability — perfect load balance by construction, no capacity
    overflow, variable experts-per-token. probs_full: (T, E).
    Returns (sel (E,C) token ids, slot_gate (E,C))."""
    scores = probs_full.T  # (E, T)
    g, sel = jax.lax.top_k(scores, C)  # per-expert top-C tokens
    return sel.astype(jnp.int32), g.astype(jnp.float32)


def dispatch_tables(
    idx: jax.Array, gates: jax.Array, E: int, C: int
) -> Tuple[jax.Array, jax.Array]:
    """Per-group dispatch bookkeeping for the padded layout.

    idx/gates: (T, k). Returns (sel (E, C) int32 token ids,
    slot_gate (E, C) fp32 combine weights). Overflow (position >= C) is
    dropped: its slot_gate is 0. Priority is token-major order (the paper /
    Megatron drop rule)."""
    T, k = idx.shape
    flat_e = idx.reshape(T * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (Tk, E)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1  # (Tk,)
    keep = pos < C
    safe_pos = jnp.where(keep, pos, C)  # overflow -> dump column C
    token_id = (jnp.arange(T * k, dtype=jnp.int32) // k).astype(jnp.int32)
    gate_flat = jnp.where(keep, gates.reshape(T * k), 0.0)

    sel = jnp.zeros((E, C + 1), jnp.int32).at[flat_e, safe_pos].set(token_id)
    slot_gate = jnp.zeros((E, C + 1), jnp.float32).at[flat_e, safe_pos].set(gate_flat)
    return sel[:, :C], slot_gate[:, :C]


# ---------------------------------------------------------------------------
# Expert FFN in the dispatcher's layout (the kernel boundary)
# ---------------------------------------------------------------------------


def expert_ffn(
    experts, xe: jax.Array, layout: DispatchLayout, use_kernel: bool = False
) -> jax.Array:
    """Apply the fused-SwiGLU expert FFN in the layout ``xe`` is in.

    * padded: ``(..., E, C, D) -> (..., E, C, D)``; Pallas ``expert_gemm``
      or the batched-einsum XLA path.
    * sorted: ``(N, D) -> (N, D)`` with ``layout.group_sizes`` rows per
      expert; Pallas group-size-aware ``grouped_gemm`` or the
      ``lax.ragged_dot`` XLA path.

    int8 experts (core/quant.py dicts carrying ``*_scale`` keys) route to
    the fused-dequant kernels on the Pallas path; the XLA paths dequantize
    the weights up front (functionally identical, no byte savings) so every
    dispatcher keeps working under quantization.
    """
    with jax.named_scope("moe_experts"):
        return _expert_ffn(experts, xe, layout, use_kernel)


def _expert_ffn(experts, xe: jax.Array, layout: DispatchLayout, use_kernel: bool) -> jax.Array:
    from repro.core.quant import dequantize_experts, is_quantized

    quant = is_quantized(experts)
    if layout.kind == "sorted":
        from repro.kernels.ops import grouped_gemm, grouped_gemm_q8, grouped_gemm_xla

        if use_kernel and quant:
            return grouped_gemm_q8(
                xe, experts["w_gate"], experts["w_up"], experts["w_down"],
                experts["w_gate_scale"], experts["w_up_scale"],
                experts["w_down_scale"], layout.group_sizes,
                row_block=layout.row_block,
            )
        if quant:
            experts = dequantize_experts(experts, xe.dtype)
        args = (xe, experts["w_gate"], experts["w_up"], experts["w_down"],
                layout.group_sizes)
        if use_kernel:
            return grouped_gemm(*args, row_block=layout.row_block)
        return grouped_gemm_xla(*args)
    if use_kernel and quant:
        from repro.kernels.ops import expert_gemm_q8

        return expert_gemm_q8(
            xe, experts["w_gate"], experts["w_up"], experts["w_down"],
            experts["w_gate_scale"], experts["w_up_scale"],
            experts["w_down_scale"],
        )
    if quant:
        experts = dequantize_experts(experts, xe.dtype)
    if use_kernel:
        from repro.kernels.ops import expert_gemm

        return expert_gemm(xe, experts["w_gate"], experts["w_up"], experts["w_down"])
    g = jnp.einsum("...ecd,edf->...ecf", xe, experts["w_gate"])
    u = jnp.einsum("...ecd,edf->...ecf", xe, experts["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
    return jnp.einsum("...ecf,efd->...ecd", h, experts["w_down"])


# ---------------------------------------------------------------------------
# Base class
# ---------------------------------------------------------------------------


class TokenDispatcher:
    """Stateless dispatch/combine pair. ``apply`` composes the pipeline
    dispatch -> expert FFN -> combine, threading the per-call
    :class:`DispatchState` between the two; dispatchers that own their
    collectives (alltoall) override ``apply`` to wrap the pipeline in
    shard_map."""

    name = "base"

    def __init__(self, cfg: Any, moe: Any, plan: Optional[FoldingPlan]):
        self.cfg, self.moe, self.plan = cfg, moe, plan

    def dispatch(
        self, x: jax.Array, idx: jax.Array, gates: jax.Array
    ) -> Tuple[jax.Array, DispatchState]:
        raise NotImplementedError

    def combine(self, ye: jax.Array, state: DispatchState) -> jax.Array:
        raise NotImplementedError

    def apply(
        self,
        experts,
        x: jax.Array,
        gates: jax.Array,
        idx: jax.Array,
        use_kernel: bool = False,
    ) -> jax.Array:
        with jax.named_scope("moe_dispatch"):
            xe, state = self.dispatch(x, idx, gates)
        ye = expert_ffn(experts, xe, state.layout, use_kernel)
        with jax.named_scope("moe_combine"):
            return self.combine(ye, state)
