#!/usr/bin/env python3
"""Compile rehearsal: each cell's timed programs, compiled at the cell's
real sizes for a described TPU v5e (no chip needed), with the bytes per
device that ``memory_analysis`` gives. Run it before a cell's first chip
run; a program that would not fit, or a kernel the chip's compiler
refuses, fails here. Each traffic kind's ``rehearse(cell, sharding)``
yields its programs, compiled.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [cell ...]
"""
import os
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def show(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"  {name}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, outputs "
          f"{m.output_size_in_bytes / 1e9:.3f} GB, temporaries {m.temp_size_in_bytes / 1e9:.3f} GB, "
          f"aliased {m.alias_size_in_bytes / 1e9:.3f} GB; peak about {total / 1e9:.3f} GB",
          flush=True)
    return total


def main(argv):
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    import json

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.ops as ops
    from benchmarks.chip import bench

    ops._interpret = lambda: False  # compile the Mosaic kernels, as on the chip
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    names = argv or [w["name"] for w in spec["workloads"] if w["chips"] == 1]
    for name in names:
        cell = bench.load_cell(name)
        print(f"{name} ({cell.config_name}, {cell.traffic['kind']}):", flush=True)
        for name, compiled in bench.traffic_kind(cell.traffic["kind"]).rehearse(cell, one):
            show(name, compiled)


if __name__ == "__main__":
    main(sys.argv[1:])
