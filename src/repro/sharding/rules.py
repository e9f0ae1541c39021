"""Logical-axis sharding rules + MoE Parallel Folding.

The paper (§3.2) decouples the parallel mapping of the Attention part
(TP x CP x DP x PP) from the MoE part (ETP x EP x EDP x PP) of each block so
that the communication-heavy groups of each part fold into the
high-bandwidth domain. On TPU we express this as a *rule table*: every
tensor dim carries a logical axis name, and the :class:`FoldingPlan` resolves
each name to mesh axes with divisibility-aware fallback. The same physical
mesh axis ('model') therefore plays

* tensor-parallel for attention tensors ('heads' -> model),
* context-parallel for attention activations when heads don't divide the
  axis ('attn_seq' -> model),
* expert-parallel for MoE tensors ('expert' -> model) when the expert count
  divides, expert-tensor-parallel otherwise ('expert_ff' -> model),

which is exactly the folding idea: attention and MoE communication both live
on the fast axis, with different logical roles per layer region.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Ordered candidate mesh-axis tuples per logical axis. ``None`` = replicated.
# Resolution picks the first candidate whose axes (a) all exist in the mesh,
# (b) are not already used by another dim of the same tensor, and (c) whose
# total size divides the dim.
RULES: Dict[str, Tuple[Optional[Tuple[str, ...]], ...]] = {
    "batch": (("pod", "data"), ("data",), None),
    # activation batch for the non-MoE (attention) part: on the paper-study
    # 3-D meshes the 'expert' axis folds into the attention DP group (MoE
    # Parallel Folding); the all-gather over 'expert' at the MoE boundary is
    # precisely Megatron's AllGather token dispatcher.
    "fold_batch": (
        ("pod", "data", "expert"), ("pod", "data"), ("data", "expert"),
        ("data",), None,
    ),
    "seq": (None,),
    # context-parallel attention activations (CP; folding for archs whose
    # head count does not divide the model axis)
    "attn_seq": (("model",), None),
    # decode-time KV cache sequence axis; prefers both axes for long_500k
    "cache_seq": (("data", "model"), ("model",), ("data",), None),
    "embed": (None,),
    "heads": (("model",), None),
    "kv_heads": (("model",), None),
    "head_dim": (None,),
    "ff": (("model",), None),
    "vocab": (("model",), None),
    "expert": (("expert",), ("model",), None),
    "expert_ff": (("model",), None),
    "layers": (None,),
    "ssm_heads": (("model",), None),
    "ssm_inner": (("model",), None),
    "ssm_group": (None,),
    "ssm_state": (None,),
    "lora": (None,),
    None: (None,),
}


def _axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


def resolve_spec(
    mesh: Mesh,
    dims: Sequence[int],
    axes: Sequence[Optional[str]],
    overrides: Optional[Dict[str, Tuple[Optional[Tuple[str, ...]], ...]]] = None,
) -> P:
    """Resolve logical axes -> PartitionSpec with divisibility fallback."""
    assert len(dims) == len(axes), (dims, axes)
    rules = dict(RULES)
    if overrides:
        rules.update(overrides)
    used: set = set()
    out = []
    for dim, name in zip(dims, axes):
        choice: Optional[Tuple[str, ...]] = None
        for cand in rules.get(name, (None,)):
            if cand is None:
                choice = None
                break
            if not all(a in mesh.shape for a in cand):
                continue
            if any(a in used for a in cand):
                continue
            if dim % _axis_size(mesh, cand) != 0:
                continue
            choice = cand
            break
        if choice is None:
            out.append(None)
        else:
            used.update(choice)
            out.append(choice if len(choice) > 1 else choice[0])
    return P(*out)


@dataclasses.dataclass(frozen=True)
class FoldingPlan:
    """Per-(config, mesh) resolved parallel layout — the folding decision.

    * ``attn_mode``: 'tp' (heads shard the model axis) or 'cp' (attention
      activations shard sequence over the model axis instead).
    * ``moe_mode``: 'ep' (experts shard the ep_axis) or 'etp' (expert FFN
      hidden dim shards the model axis).
    * ``ep_axis``: mesh axis playing expert-parallel ('expert' on the
      paper-study 3-D meshes, 'model' on the production 2-D mesh).
    """

    mesh: Mesh
    attn_mode: str
    moe_mode: str
    ep_axis: Optional[str]
    ep_size: int
    batch_axes: Tuple[str, ...]
    # FSDP/ZeRO-3: additionally shard every weight's largest free dim over
    # 'data' (for archs whose TP/EP-sharded weights alone exceed HBM).
    fsdp: bool = False

    @staticmethod
    def make(cfg: Any, mesh: Mesh) -> "FoldingPlan":
        # the plan places tensors through sharding constraints and lets the
        # compiler propagate, which JAX allows only on Auto axes; a mesh
        # from jax.make_mesh (Explicit axes by default) is re-typed
        if any(t != AxisType.Auto for t in mesh.axis_types):
            mesh = Mesh(mesh.devices, mesh.axis_names,
                        axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        model_size = mesh.shape.get("model", 1)
        heads = getattr(cfg, "num_heads", 0)
        attn_mode = "tp" if heads and heads % model_size == 0 else "cp"
        moe_mode, ep_axis, ep_size = "etp", None, 1
        if getattr(cfg, "moe", None) is not None:
            E = cfg.moe.num_experts
            if "expert" in mesh.shape and E % mesh.shape["expert"] == 0:
                moe_mode, ep_axis, ep_size = "ep", "expert", mesh.shape["expert"]
            elif E % model_size == 0:
                moe_mode, ep_axis, ep_size = "ep", "model", model_size
        batch_axes = tuple(
            a for a in ("pod", "data") if a in mesh.shape
        )
        return FoldingPlan(
            mesh, attn_mode, moe_mode, ep_axis, ep_size, batch_axes,
            fsdp=bool(getattr(cfg, "fsdp", False)),
        )

    # -- activation constraint helpers ------------------------------------
    def spec(self, dims: Sequence[int], *axes: Optional[str]) -> P:
        return resolve_spec(self.mesh, dims, axes)

    def constrain(self, x: jax.Array, *axes: Optional[str]) -> jax.Array:
        spec = resolve_spec(self.mesh, x.shape, axes)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def sharding(self, dims: Sequence[int], *axes: Optional[str]) -> NamedSharding:
        return NamedSharding(self.mesh, resolve_spec(self.mesh, dims, axes))


# ---------------------------------------------------------------------------
# PartitionSpec <-> JSON (checkpoint manifests record the spec each leaf was
# SAVED under; restore re-resolves specs for the TARGET mesh via the decl
# tables above, so the recorded spec is provenance, not a constraint).
# ---------------------------------------------------------------------------


def spec_to_json(spec: Optional[P]) -> Optional[list]:
    if spec is None:
        return None
    return [list(p) if isinstance(p, tuple) else p for p in spec]


def spec_from_json(obj: Optional[Sequence]) -> Optional[P]:
    if obj is None:
        return None
    return P(*[tuple(p) if isinstance(p, list) else p for p in obj])


# ---------------------------------------------------------------------------
# Parameter declarations: single source of truth for shape/init/sharding.
# ---------------------------------------------------------------------------

InitFn = Callable[[jax.Array, Tuple[int, ...], Any], jax.Array]


def _fan_in_normal(scale: float = 1.0) -> InitFn:
    def init(key, shape, dtype):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        std = scale / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    return init


def _normal(std: float) -> InitFn:
    def init(key, shape, dtype):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    return init


def _zeros(key, shape, dtype):
    return jnp.zeros(shape, dtype)


def _ones(key, shape, dtype):
    return jnp.ones(shape, dtype)


INITS: Dict[str, Callable[..., InitFn]] = {
    "fan_in": _fan_in_normal,
    "normal": _normal,
}


@dataclasses.dataclass(frozen=True)
class ParamDecl:
    """Declarative parameter: shape + logical axes + init + dtype."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "fan_in"  # fan_in | normal:<std> | zeros | ones
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    def init_fn(self) -> InitFn:
        if self.init == "zeros":
            return _zeros
        if self.init == "ones":
            return _ones
        if self.init.startswith("normal"):
            std = float(self.init.split(":")[1]) if ":" in self.init else 0.02
            return _normal(std)
        if self.init.startswith("uniform"):
            _, lo, hi = self.init.split(":")
            lo, hi = float(lo), float(hi)

            def init(key, shape, dtype):
                return jax.random.uniform(
                    key, shape, jnp.float32, lo, hi
                ).astype(dtype)

            return init
        return _fan_in_normal(1.0)


def _is_decl(x) -> bool:
    return isinstance(x, ParamDecl)


def _used_axes(parts) -> set:
    used = set()
    for p in parts:
        if p is None:
            continue
        used.update(p if isinstance(p, tuple) else (p,))
    return used


def fsdp_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh, axis: str = "data") -> P:
    """Add data(+pod) sharding to the largest free divisible dim (ZeRO-1/3).
    On the multi-pod mesh the 'pod' axis joins the group so optimizer/FSDP
    state scales with the full data-parallel world size."""
    cand = tuple(
        a for a in (("pod", axis) if "pod" in mesh.shape else (axis,))
        if a in mesh.shape and mesh.shape[a] > 1
    )
    parts = list(spec) + [None] * (len(shape) - len(spec))
    used = _used_axes(parts)
    cand = tuple(a for a in cand if a not in used)
    if not cand:
        return spec
    # try the joint (pod, data) group first, then progressively smaller
    for group in (cand,) + ((cand[-1:],) if len(cand) > 1 else ()):
        size = int(np.prod([mesh.shape[a] for a in group]))
        best, best_dim = -1, 0
        for i, (dim, p) in enumerate(zip(shape, parts)):
            if p is None and dim % size == 0 and dim > best_dim:
                best, best_dim = i, dim
        if best >= 0:
            parts[best] = group if len(group) > 1 else group[0]
            return P(*parts)
    return spec


def _resolve_decl(d: ParamDecl, plan: "FoldingPlan", overrides=None) -> P:
    spec = resolve_spec(plan.mesh, d.shape, d.axes, overrides)
    if plan.fsdp and "layers" in d.axes:  # weights only, not caches/scalars
        spec = fsdp_spec(spec, d.shape, plan.mesh)
    return spec


def init_from_decls(decls, key: jax.Array):
    """Materialize a pytree of ParamDecl into concrete parameters."""
    leaves, treedef = jax.tree.flatten(decls, is_leaf=_is_decl)
    keys = jax.random.split(key, len(leaves))
    vals = [d.init_fn()(k, d.shape, d.dtype) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_from_decls(decls):
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), decls, is_leaf=_is_decl
    )


def specs_from_decls(decls, plan: FoldingPlan, overrides=None):
    return jax.tree.map(
        lambda d: _resolve_decl(d, plan, overrides), decls, is_leaf=_is_decl
    )


def shardings_from_decls(decls, plan: FoldingPlan, overrides=None):
    return jax.tree.map(
        lambda d: NamedSharding(plan.mesh, _resolve_decl(d, plan, overrides)),
        decls,
        is_leaf=_is_decl,
    )
