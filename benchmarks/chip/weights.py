"""The program's parameters, made on the device from the seed.

The dense parent's weights come from ``gen.py`` (the benchmark's own
generator) in the program's tree layout, and the program's online upcycling
(``repro.core.upcycle.upcycle_params``) turns them into the MoE, all in one
jitted call. The router is set from ``gen.py`` too, so the reference knows
every weight without reading one from the program.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import gen

# gen.py's per-layer names -> the program's block tree (``stack/slot0``)
_BLOCK = {
    "norm1.scale": ("norm1", "scale"), "norm1.bias": ("norm1", "bias"),
    "norm2.scale": ("norm2", "scale"), "norm2.bias": ("norm2", "bias"),
    "wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
    "wo": ("mixer", "wo"), "bq": ("mixer", "bq"), "bk": ("mixer", "bk"),
    "bv": ("mixer", "bv"),
    "w_gate": ("ffn", "w_gate"), "w_up": ("ffn", "w_up"),
    "w_down": ("ffn", "w_down"),
}


def dense_params(key: jax.Array, d: gen.Dims, decl_dtypes: Dict) -> Dict[str, Any]:
    """The dense parent in the program's layout; each leaf in the type the
    program declares for it."""
    top = gen.top_weights(key, d)
    layers = [gen.layer_weights(key, d, l) for l in range(d.num_layers)]
    block: Dict[str, Dict[str, jax.Array]] = {}
    for name, (grp, leaf) in _BLOCK.items():
        if name not in layers[0]:
            continue
        block.setdefault(grp, {})[leaf] = jnp.stack([w[name] for w in layers])
    params = {
        "embed": {"embedding": top["embedding"], "unembedding": top["unembedding"]},
        "final_norm": {"scale": top["final_norm.scale"]},
        "stack": {"slot0": block},
    }
    if "final_norm.bias" in top:
        params["final_norm"]["bias"] = top["final_norm.bias"]
    return jax.tree.map(lambda x, dt: x.astype(dt), params, decl_dtypes)


def decl_dtypes(cfg) -> Dict:
    from repro.models.model import model_decl
    from repro.sharding.rules import ParamDecl

    return jax.tree.map(lambda p: jnp.dtype(p.dtype), model_decl(cfg),
                        is_leaf=lambda p: isinstance(p, ParamDecl))


def make_init(dense_cfg, moe_cfg, d: gen.Dims):
    """A jitted ``build(seed_key) -> MoE params``: the dense parent from the
    seed, the program's upcycling, the benchmark's router."""
    from repro.core.upcycle import upcycle_params

    dense_dt = decl_dtypes(dense_cfg)
    moe_dt = decl_dtypes(moe_cfg)

    def build(key):
        dense = dense_params(key, d, dense_dt)
        moe = upcycle_params(dense_cfg, moe_cfg, dense, jax.random.fold_in(key, 7))
        ffn = moe["stack"]["slot0"]["ffn"]
        ffn["router"]["w_g"] = jnp.stack(
            [gen.layer_weights(key, d, l)["router"] for l in range(d.num_layers)]
        ).astype(moe_dt["stack"]["slot0"]["ffn"]["router"]["w_g"])
        return moe

    return jax.jit(build)


def check_tree(params, cfg) -> None:
    """The built tree has exactly the program's declared leaves, shapes and
    types (a layout drift between gen.py's names and the program's fails
    here, before any run)."""
    from repro.models.model import model_decl
    from repro.sharding.rules import abstract_from_decls

    want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract_from_decls(model_decl(cfg)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or want != got:
        raise ValueError(f"built parameters do not match the program's "
                         f"declaration:\nwant {want}\ngot  {got}")
