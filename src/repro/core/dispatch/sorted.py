"""Sorted dropless dispatcher (MegaBlocks/Megatron-style argsort permutation).

Instead of the dense padded ``(E, C, D)`` layout — which for dropless runs
degenerates to the worst case ``C = T`` — the token assignments are argsorted
by expert id into one flat ``(T*k, D)`` expert-sorted buffer plus per-expert
``group_sizes``. True dropless: every assignment is computed, no capacity,
no ``O(T*k*E)`` one-hot/cumsum table build (the permutation is an
``O(N log N)`` argsort + gather), and compute/memory scale with ``T*k``
instead of ``E*C``.

Layout notes for the kernel path: the Pallas grouped GEMM tiles rows, so
each expert's region is aligned up to the row-tile size (``row_block``) and
rows past ``group_sizes[e]`` in the last tile are masked. The XLA path
(``lax.ragged_dot``) consumes the compact buffer (``row_block=1``).

This dispatcher operates in the global pjit view (like allgather); under an
EP mesh XLA inserts the gather/reduce collectives. A shard_map variant with
explicit a2a of the sorted buffer is future work.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.dispatch.base import (
    DispatchLayout,
    DispatchState,
    TokenDispatcher,
    expert_ffn,
)

# Row-tile alignment of the expert-sorted buffer on the kernel path. This is
# the single knob: it is threaded to the grouped GEMM as its row-tile size
# via layout.row_block -> ops.grouped_gemm(row_block=...), so buffer
# alignment and kernel tiling cannot drift apart. 128 = MXU-aligned.
KERNEL_ROW_BLOCK = 128


def aligned_rows(N: int, E: int, row_block: int) -> int:
    """Static worst-case buffer rows: sum_e ceil(g_e/b)*b <= N + E*(b-1),
    rounded up to a whole number of row tiles."""
    if row_block <= 1:
        return N
    return -(-(N + E * (row_block - 1)) // row_block) * row_block


class SortedDispatcher(TokenDispatcher):
    name = "sorted"

    def _indices(self, idx: jax.Array, gates: jax.Array, row_block: int):
        """Shared routing-index computation: the stable expert-major sort and
        the (token, slot, dest, gate_sorted, group_sizes) vectors both the
        materializing and the fused paths consume."""
        T = idx.shape[0]
        E = self.moe.num_experts
        k = idx.shape[-1]
        N = T * k
        b = row_block

        flat_e = idx.reshape(N)
        # stable argsort: expert-major, token-major within an expert (same
        # priority order as the table-based dispatchers)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        token = order // k  # token providing each sorted row
        slot = (order % k).astype(jnp.int32)  # its top-k slot (unique pair)
        group_sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)

        # destination row of each sorted assignment in the (tile-aligned)
        # buffer: expert region start + position within the expert
        padded = ((group_sizes + b - 1) // b) * b
        starts_pad = jnp.cumsum(padded) - padded
        starts = jnp.cumsum(group_sizes) - group_sizes
        pos_in_group = jnp.arange(N, dtype=jnp.int32) - starts[sorted_e]
        dest = (starts_pad[sorted_e] + pos_in_group).astype(jnp.int32)
        gate_sorted = gates.reshape(N)[order]
        return token, slot, dest, gate_sorted, group_sizes

    def dispatch(
        self, x: jax.Array, idx: jax.Array, gates: jax.Array, row_block: int = 1
    ):
        T, D = x.shape
        E = self.moe.num_experts
        N = T * idx.shape[-1]
        token, slot, dest, gate_sorted, group_sizes = self._indices(
            idx, gates, row_block
        )

        N_pad = aligned_rows(N, E, row_block)
        xs = jnp.zeros((N_pad, D), x.dtype).at[dest].set(x[token])
        state = DispatchState(
            layout=DispatchLayout(
                "sorted", E, group_sizes=group_sizes, row_block=row_block
            ),
            residuals={
                "token": token,
                "dest": dest,
                "gate_sorted": gate_sorted,
            },
            static={"tokens": T},
        )
        return xs, state

    def combine(self, ye: jax.Array, state) -> jax.Array:
        D = ye.shape[-1]
        r = state.residuals
        # fp32 accumulation for the k-way scatter-add (a bf16 accumulator
        # loses ~2 bits over k partial sums); cast once at the end
        yv = ye[r["dest"]].astype(jnp.float32)  # (N, D) valid rows, sorted order
        yv = yv * r["gate_sorted"][:, None].astype(jnp.float32)
        T = state.static["tokens"]
        out = jnp.zeros((T, D), jnp.float32).at[r["token"]].add(yv)
        return out.astype(ye.dtype)

    def _apply_fused(
        self, experts, x: jax.Array, gates: jax.Array, idx: jax.Array
    ) -> jax.Array:
        """Dispatch-in-kernel path: the gather runs in the grouped GEMM's
        prologue and the gate-weighted combine in its epilogue, so neither
        the permuted (N_pad, D) buffer nor the (N, D) gathered output is
        materialized in HBM."""
        from repro.core.quant import is_quantized
        from repro.kernels import ops

        with jax.named_scope("moe_dispatch"):
            token, slot, dest, gate_sorted, group_sizes = self._indices(
                idx, gates, KERNEL_ROW_BLOCK
            )
        if is_quantized(experts):
            return ops.grouped_gemm_fused_q8(
                x,
                experts["w_gate"], experts["w_up"], experts["w_down"],
                experts["w_gate_scale"], experts["w_up_scale"],
                experts["w_down_scale"],
                group_sizes, token, dest, slot, gate_sorted,
                row_block=KERNEL_ROW_BLOCK,
            )
        return ops.grouped_gemm_fused(
            x,
            experts["w_gate"], experts["w_up"], experts["w_down"],
            group_sizes, token, dest, slot, gate_sorted,
            row_block=KERNEL_ROW_BLOCK,
        )

    def apply(
        self,
        experts,
        x: jax.Array,
        gates: jax.Array,
        idx: jax.Array,
        use_kernel: bool = False,
    ) -> jax.Array:
        if use_kernel and getattr(self.moe, "fused_dispatch", False):
            # gather and combine run inside the kernel
            with jax.named_scope("moe_experts"):
                return self._apply_fused(experts, x, gates, idx)
        # the kernel tiles rows -> tile-aligned regions; XLA ragged_dot
        # consumes the compact buffer
        row_block = KERNEL_ROW_BLOCK if use_kernel else 1
        with jax.named_scope("moe_dispatch"):
            xe, state = self.dispatch(x, idx, gates, row_block=row_block)
        ye = expert_ffn(experts, xe, state.layout, use_kernel)
        with jax.named_scope("moe_combine"):
            return self.combine(ye, state)
