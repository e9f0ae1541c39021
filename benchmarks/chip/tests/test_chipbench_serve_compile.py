"""Compile rehearsal of the serving cell: its prefill-chunk and decode
programs at the published widths, with the cell's batch, chunk, block
table and page pool, compiled for a described TPU v5e chip (no chip
needed). Both carry their Pallas kernels, and each fits one chip's 16 GB:
this is what fixes ``max_batch`` and ``num_pages`` in the configuration
and guards them."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.chip import bench, flops  # noqa: E402

CELL = "serve.qwen25-e8t2.chat"
KERNELS = {"prefill chunk": ("grouped_gemm",), "decode": ("grouped_gemm", "paged_attention")}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled(topo):
    import jax

    import repro.kernels.ops as ops
    from benchmarks.chip.kinds import serve

    mp = pytest.MonkeyPatch()
    mp.setattr(ops, "_interpret", lambda: False)  # compile the Mosaic kernels
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # no chip to read it back
    try:
        yield dict(serve.programs(bench.load_cell(CELL), SingleDeviceSharding(topo.devices[0])))
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        mp.undo()


@pytest.mark.parametrize("program", sorted(KERNELS))
def test_program_carries_its_kernels_and_fits(compiled, program):
    c = compiled[program]
    text = c.as_text()
    assert "tpu_custom_call" in text
    for k in KERNELS[program]:
        assert k in text, (program, k)
    m = c.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
             - m.alias_size_in_bytes)
    assert total < flops.peaks("TPU v5 lite")["hbm_bytes"], (program, total)
