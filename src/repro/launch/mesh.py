"""Device meshes.

``make_production_mesh`` — the deliverable mesh: 16x16 ('data','model') per
pod, 2x16x16 ('pod','data','model') for the two-pod run. A function, not a
module constant, so importing this module never touches jax device state.

``make_study_mesh`` — paper-study 3-D meshes ('data','expert','model') used
by the Table-2 folding benchmarks, where the attention layers fold the
'expert' axis into their data-parallel group while the MoE layers use it as
EP (the paper's TP2CP2 <-> TP1EP8 example).

``make_serving_mesh`` — EP x DP ('data', 'expert') mesh for the sharded
serving engine (``ServingEngine(mesh=...)``).

Every mesh is built with Auto axis types: the model code places tensors
with ``with_sharding_constraint`` / ``NamedSharding`` and lets the compiler
propagate (``sharding/rules.py``), which JAX accepts only on Auto axes
(``jax.make_mesh`` now defaults to Explicit ones).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices) -> Mesh:
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    assert len(devices) >= n, (
        f"need {n} devices (run under XLA_FLAGS=--xla_force_host_platform_device_count=512); "
        f"have {len(devices)}"
    )
    return _auto_mesh(shape, axes, devices[:n])


def make_study_mesh(data: int, expert: int, model: int) -> Mesh:
    n = data * expert * model
    devices = jax.devices()
    assert len(devices) >= n, (n, len(devices))
    return _auto_mesh((data, expert, model), ("data", "expert", "model"), devices[:n])


def make_host_mesh() -> Mesh:
    """1x1 mesh on the real local device — used by tests/examples so the
    sharding code paths run identically at laptop scale."""
    return _auto_mesh((1, 1), ("data", "model"), jax.devices()[:1])


def make_serving_mesh(dp: int = 1, ep: int = 1) -> Mesh:
    """EP x DP serving mesh: ('data', 'expert') with ``dp * ep`` devices.
    The 'data' axis shards the decode batch rows and the KV page pool (one
    sub-pool stride per DP shard); 'expert' plays expert-parallel for the
    MoE FFN weights and the decode all-to-all. Run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for CPU tests."""
    n = dp * ep
    devices = jax.devices()
    assert len(devices) >= n, (
        f"serving mesh dp={dp} x ep={ep} needs {n} devices; have "
        f"{len(devices)} (set --xla_force_host_platform_device_count)"
    )
    return _auto_mesh((dp, ep), ("data", "expert"), devices[:n])
