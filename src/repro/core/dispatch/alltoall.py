"""AllToAll token dispatchers (shard_map over the EP axis; preferred for
small top-k, per the paper §3.2 practice #2).

Each token shard builds its local dispatch tables, sends capacity-sized
slot blocks to the shards owning the target experts, and the combine
reverses the exchange. Requires an EP plan (``plan.moe_mode == "ep"``) and
a token count divisible by the token-shard product; `get_dispatcher` falls
back to allgather otherwise (loudly — and serving mode treats the fallback
as a config error, see ``MoEConfig.strict_dispatch``).

Two exchange schedules over the same dispatch tables:

* :class:`AllToAllDispatcher` (``"alltoall"``) — one monolithic
  ``lax.all_to_all`` each way. The whole exchange must complete before any
  expert FFN row is computed, so dispatch latency is fully exposed.
* :class:`OverlapAllToAllDispatcher` (``"a2a_overlap"``) — the exchange is
  decomposed into ``ep - 1`` shifted ``lax.ppermute`` rounds, double-
  buffered against expert compute: the block exchanged in round ``r`` has
  no data dependence on the FFN of round ``r - 1``, so the compiler's async
  collectives (``collective-permute-start``/``-done`` on TPU) run each hop
  while the previous block's grouped GEMM executes. This is the serving
  decode schedule — the paper's overlapped-dispatch practice (§3.2) —
  where hiding the all-to-all behind attention/FFN compute is what keeps
  EP decode latency dense-like.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.dispatch.base import (
    DispatchLayout,
    DispatchState,
    TokenDispatcher,
    capacity,
    dispatch_tables,
    expert_ffn,
)


class AllToAllDispatcher(TokenDispatcher):
    name = "alltoall"

    def dispatch(self, x: jax.Array, idx: jax.Array, gates: jax.Array, *,
                 E: int, C: int, ep: int, E_loc: int, ep_axis: str):
        """Local shard view: table build + all_to_all. Called inside the
        shard_map region set up by ``apply`` (which supplies the static
        shard geometry)."""
        T_loc, D = x.shape
        sel, slot_gate = dispatch_tables(idx, gates, E, C)  # (E, C)
        send = x[sel]  # (E, C, D) outgoing slots, grouped by global expert
        recv = jax.lax.all_to_all(
            send.reshape(ep, E_loc, C, D), ep_axis, split_axis=0, concat_axis=0
        )  # (ep, E_loc, C, D): slot block from every sender for my experts
        xe = recv.transpose(1, 0, 2, 3).reshape(E_loc, ep * C, D)
        state = DispatchState(
            layout=DispatchLayout("padded", E_loc, capacity=ep * C),
            residuals={"sel": sel, "slot_gate": slot_gate},
            static={"tokens": T_loc, "E": E, "C": C, "ep": ep, "ep_axis": ep_axis},
        )
        return xe, state

    def combine(self, ye: jax.Array, state) -> jax.Array:
        r, st = state.residuals, state.static
        E, C, ep = st["E"], st["C"], st["ep"]
        E_loc = state.layout.num_experts
        D = ye.shape[-1]
        back = ye.reshape(E_loc, ep, C, D).transpose(1, 0, 2, 3)
        ret = jax.lax.all_to_all(back, st["ep_axis"], split_axis=0, concat_axis=0)
        ret = ret.reshape(E, C, D) * r["slot_gate"][..., None].astype(ye.dtype)
        return jnp.zeros((st["tokens"], D), ret.dtype).at[
            r["sel"].reshape(E * C)
        ].add(ret.reshape(E * C, D))

    def apply(
        self,
        experts,
        x: jax.Array,
        gates: jax.Array,
        idx: jax.Array,
        use_kernel: bool = False,
    ) -> jax.Array:
        plan, moe = self.plan, self.moe
        mesh = plan.mesh
        ep_axis = plan.ep_axis
        assert ep_axis is not None and plan.moe_mode == "ep"
        ep = mesh.shape[ep_axis]
        T = x.shape[0]
        E = moe.num_experts
        token_axes = tuple(plan.batch_axes) + (ep_axis,)
        shards = int(np.prod([mesh.shape[a] for a in token_axes]))
        assert T % shards == 0, (T, shards)
        E_loc = E // ep
        C = capacity(moe, T // shards)

        w_specs = jax.tree.map(lambda _: P(ep_axis, None, None), experts)

        def local_moe(x_l, gates_l, idx_l, experts_l):
            return self._local_pipeline(
                x_l, gates_l, idx_l, experts_l,
                E=E, C=C, ep=ep, E_loc=E_loc, ep_axis=ep_axis,
                use_kernel=use_kernel,
            )

        fn = jax.shard_map(
            local_moe,
            mesh=mesh,
            in_specs=(
                P(token_axes, None), P(token_axes, None), P(token_axes, None), w_specs,
            ),
            out_specs=P(token_axes, None),
            check_vma=False,
        )
        return fn(x, gates, idx, experts)

    def _local_pipeline(self, x_l, gates_l, idx_l, experts_l, *,
                        E, C, ep, E_loc, ep_axis, use_kernel):
        """Per-shard dispatch -> expert FFN -> combine (inside shard_map).
        Subclasses override this to change the exchange schedule."""
        with jax.named_scope("moe_dispatch"):
            xe, state = self.dispatch(
                x_l, idx_l, gates_l, E=E, C=C, ep=ep, E_loc=E_loc, ep_axis=ep_axis
            )
        ye = expert_ffn(experts_l, xe[None], state.layout, use_kernel)[0]
        with jax.named_scope("moe_combine"):
            return self.combine(ye, state)


class OverlapAllToAllDispatcher(AllToAllDispatcher):
    """Double-buffered ring schedule: the all-to-all is decomposed into
    ``ep - 1`` shifted ``ppermute`` hops, each independent of the expert
    FFN on the previously received block, so exchange and compute overlap.

    Round ``r`` (0 <= r < ep): shard ``i`` sends the slot block destined to
    shard ``(i + r) % ep`` directly to it (round 0 is the local block — no
    exchange), runs the expert FFN on the block received from shard
    ``(i - r) % ep``, and returns the previous round's result with the
    inverse shift. Per-round blocks are ``(E_loc, C, D)`` — the padded
    expert FFN is slot-wise, so chunking capacity by source shard is
    numerically identical to the monolithic ``(E_loc, ep*C, D)`` GEMM."""

    name = "a2a_overlap"

    def _local_pipeline(self, x_l, gates_l, idx_l, experts_l, *,
                        E, C, ep, E_loc, ep_axis, use_kernel):
        T_loc, D = x_l.shape
        with jax.named_scope("moe_dispatch"):
            sel, slot_gate = dispatch_tables(idx_l, gates_l, E, C)  # (E, C)
            send = x_l[sel].reshape(ep, E_loc, C, D)  # [j] = slots for shard j
            my = jax.lax.axis_index(ep_axis)
            # rolled[r] = block destined to shard (my + r) % ep; round 0 local
            rolled = jnp.roll(send, -my, axis=0)
        layout = DispatchLayout("padded", E_loc, capacity=C)
        outs = []
        for r in range(ep):
            if r == 0:
                blk = rolled[0]
            else:
                with jax.named_scope("moe_dispatch"):
                    blk = jax.lax.ppermute(
                        rolled[r], ep_axis, [(i, (i + r) % ep) for i in range(ep)]
                    )  # arrives from shard (my - r) % ep: its slots for my experts
            ye = expert_ffn(experts_l, blk[None], layout, use_kernel)[0]
            if r == 0:
                outs.append(ye)
            else:
                with jax.named_scope("moe_combine"):
                    outs.append(jax.lax.ppermute(
                        ye, ep_axis, [(i, (i - r) % ep) for i in range(ep)]
                    ))  # back to its source: my block processed by (my + r) % ep
        with jax.named_scope("moe_combine"):
            # outs[r] holds results for global experts of shard (my + r) % ep;
            # un-roll to expert-shard-major order matching ``sel``
            ret = jnp.roll(jnp.stack(outs), my, axis=0).reshape(E, C, D)
            ret = ret * slot_gate[..., None].astype(ret.dtype)
            return jnp.zeros((T_loc, D), ret.dtype).at[
                sel.reshape(E * C)
            ].add(ret.reshape(E * C, D))
