"""The reduction from a profiler trace to device numbers, on a small trace
recorded on a TPU v5e (a one-layer upcycled training step with the
flash-attention and grouped-GEMM kernels, then a paged decode step, each
twice, under harness-style ``bench.*`` spans) and on hand-made ops."""
import os
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from benchmarks.chip import trace  # noqa: E402
from benchmarks.chip.trace import Op, Span, Trace  # noqa: E402

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def small():
    return trace.load(str(SMALL))


def raw_ops():
    """The device ops read straight from the file, without trace.py."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(SMALL))
    out = []
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
    return out


def test_loads_device_ops_and_harness_spans(small):
    assert list(small.devices) == ["/device:TPU:0"]
    assert len(small.devices["/device:TPU:0"]) == len(raw_ops()) == 1940
    assert [s.name for s in small.spans] == ["bench.step_fn", "bench.decode"] * 2


def test_busy_is_the_union_of_op_intervals(small):
    """Against a sweep over the raw events' boundaries."""
    lo, hi = small.window()
    # starts before ends at one instant, so an op of no length is counted
    edges = sorted([(s, -1) for _, s, d in raw_ops()] + [(s + d, 1) for _, s, d in raw_ops()])
    busy, depth, since = 0.0, 0, None
    for t, end in edges:
        if depth == 0 and end == -1:
            since = t
        depth -= end
        if depth == 0:
            busy += t - since
    assert trace.busy_ns(small.devices["/device:TPU:0"], lo, hi) == pytest.approx(busy, rel=1e-9)
    assert trace.mean_busy_ns(small, lo, hi) < hi - lo


@pytest.mark.parametrize("family,prefix", [
    ("grouped_gemm", "%grouped_gemm."), ("flash_attention", "%flash_attention."),
    ("paged_attention", "%paged_attention."),
])
def test_kernel_time_by_name(small, family, prefix):
    lo, hi = small.window()
    want = sum(d for n, s, d in raw_ops() if n.startswith(prefix))
    got, ops = trace.kernel_ns(small, (family,), lo, hi)
    assert want > 0 and got == pytest.approx(want)
    assert all(o.name.startswith(family + ".") for o in ops)


def test_op_names():
    assert trace.op_name("%grouped_gemm.46 = bf16[3072,2048] custom-call(%x)") == "grouped_gemm.46"
    assert Op("all-to-all-start.3", "", 0, 1).family == "all-to-all-start"


def ops(*spans):
    return [Op(n, n, float(s), float(e - s)) for n, s, e in spans]


def test_exposed_collective_time():
    """All-to-all 10-30 under compute 0-15 and 25-40: exposed 15-25."""
    dev = ops(("fusion.1", 0, 15), ("all-to-all.2", 10, 30), ("fusion.3", 25, 40))
    assert trace.exposed_collective_ns(dev, 0, 40) == pytest.approx(10.0)
    assert trace.exposed_collective_ns(dev, 0, 20) == pytest.approx(5.0)
    assert trace.exposed_collective_ns(ops(("fusion.1", 0, 5)), 0, 5) == 0.0


def test_idle_gaps_attributed_to_the_innermost_span():
    dev = ops(("fusion.1", 0, 10), ("fusion.2", 20, 30), ("fusion.3", 60, 70))
    tr = Trace({"/device:TPU:0": dev},
               [Span("bench.window", 0, 100), Span("bench.engine_step", 15, 10)])
    assert trace.idle_gaps(dev, 0, 100) == [(10, 20), (30, 60), (70, 100)]
    got = dict(trace.attribute_gaps(tr, 0, 100))
    assert got == pytest.approx({"bench.engine_step": 10e-9, "bench.window": 60e-9})
    assert trace.busy_ns(dev, 0, 100) == 30.0
    assert dict(trace.top_ops(tr, 0, 100)) == pytest.approx({"fusion": 30e-9})
