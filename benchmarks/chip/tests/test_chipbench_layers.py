"""The train step's device time by layer (``layers.py`` and the seven scope
readers) and the host loop's spans (``host_serial_ms.train``), on hand-made
traces; and the spans ``Trainer.run`` writes, under the profiler on the
CPU."""
import glob
import os
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from benchmarks.chip import bench, layers, trace  # noqa: E402
from benchmarks.chip.trace import Op, Span, Trace  # noqa: E402

SCOPE_READERS = {"attention_ms.train": "attention", "router_ms.train": "moe_router",
                 "moe_dispatch_ms.train": "moe_dispatch", "expert_ffn_ms.train": "moe_experts",
                 "moe_combine_ms.train": "moe_combine", "head_loss_ms.train": "head_loss",
                 "anomaly_guard_ms.train": "anomaly_guard"}

FWD = "jit(step)/jvp(model)/layer_scan/while/body/closed_call/"
BWD = "jit(step)/transpose(jvp(model))/layer_scan/while/body/closed_call/checkpoint/"
REMAT = BWD + "rematted_computation/"
# (family, op-name path as a TPU trace gives it, start, end) of one step
STEP = [
    ("while", "jit(step)/jvp(model)/layer_scan/while:", 0, 100),
    ("fusion", FWD + "norm/mul:", 2, 5),
    ("flash_attention", FWD + "attention/jit(flash_attention)/pallas_call:", 5, 25),
    ("fusion", FWD + "moe_router/dot_general:", 25, 30),
    ("sort", FWD + "moe_dispatch/jit(argsort)/sort:", 30, 35),
    ("grouped_gemm", FWD + "moe_experts/jit(grouped_gemm)/pallas_call:", 35, 60),
    ("scatter", FWD + "moe_combine/scatter-add:", 60, 65),
    ("dynamic-slice_bitcast_fusion", "jit(step)/jvp(model)/layer_scan/while/body/dynamic_slice:",
     70, 75),
    ("broadcast", "jit(step)/jvp(model)/broadcast_in_dim:", 100, 104),
    ("fusion", "jit(step)/jvp(model)/head_loss/dot_general:", 104, 110),
    ("fusion", "jit(step)/transpose(jvp(model))/head_loss/dot_general:", 110, 120),
    ("flash_attention", REMAT + "attention/jit(flash_attention)/pallas_call:", 120, 130),
    ("flash_attention", BWD + "attention/jit(flash_attention)/pallas_call:", 130, 142),
    ("grouped_gemm", BWD + "moe_experts/jit(grouped_gemm)/pallas_call:", 142, 150),
    ("fusion", BWD + "moe_router/add_any:", 150, 151),
    ("fusion", BWD + "moe_dispatch/scatter-add:", 151, 152),
    ("fusion", BWD + "moe_combine/gather:", 152, 153),
    ("scatter", "jit(step)/transpose(jvp(model))/embed/scatter-add:", 153, 156),
    ("fusion", "jit(step)/jvp(model)/embed/gather:", 156, 157),
    ("fusion", "jit(step)/adamw/mul:", 160, 170),
    ("multiply_reduce_fusion", "jit(step)/grad_norm/reduce_sum:", 170, 172),
    ("convert_select_fusion", "jit(step)/anomaly_guard/jit(_where)/select_n:", 172, 180),
    ("fusion", "jit(step)/add:", 180, 182),
    ("copy", "", 185, 190),  # no path: phase other
]


def step_trace(step=STEP, steps=2, spans=()):
    dev = [Op(f"{fam}.{i}", "", float(s + k * 200), float(e - s), path)
           for k in range(steps) for i, (fam, path, s, e) in enumerate(step)]
    return Trace({"/device:TPU:0": sorted(dev, key=lambda o: o.start_ns)},
                 [Span("bench.window", 0.0, 200.0 * steps), *spans])


def ctx_of(tr, steps=2, num_experts=8):
    lo, hi = tr.window() if not tr.spans else trace.span_window(tr, "bench.window")
    return {"kind": "train", "trace": tr, "lo": lo, "hi": hi, "steps": steps, "remat": "full",
            "dims": types.SimpleNamespace(num_experts=num_experts)}


def device_of(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    return {"busy_s": trace.mean_busy_ns(ctx["trace"], lo, hi) * 1e-9,
            "window_s": (hi - lo) * 1e-9}


@pytest.mark.parametrize("path,layer", [
    (FWD + "moe_experts/jit(grouped_gemm)/pallas_call:", "moe_experts"),
    (REMAT + "attention/bsd,dhk->bshk/dot_general:", "attention"),
    ("jit(step)/transpose(jvp(model))/head_loss/mul", "head_loss"),
    ("jit(step)/jvp(model)/layer_scan/while/body/dynamic_slice:", "layer_scan"),
    ("jit(step)/transpose(jvp(model))/broadcast_in_dim:", "unscoped"),
    ("jit(step)/jvp(jit(take_along_axis))/select_n:", "unscoped"),
    ("jit(step)/transpose(jvp(attention))/mul:", "attention"),
    ("", "unscoped"),
])
def test_layer_is_the_innermost_named_segment(path, layer):
    assert layers.layer_of(path) == layer


def test_layers_and_unscoped_make_up_each_phase():
    """Every phase's layers and ``unscoped`` sum to the phase as
    ``read_trace`` gives it; with ``other`` and idle they make up the
    window; the nested kernel counts in its layer, the enclosing ``while``
    by its self time in ``layer_scan``."""
    from benchmarks.chip.kinds import train

    ctx = ctx_of(step_trace())
    device = device_of(ctx)
    ctx = train.read_trace(ctx, device)
    ms = layers.layer_ms(ctx)
    assert set(ms) == set(trace.PHASES)
    for p in trace.PHASES:
        assert sum(ms[p].values()) == pytest.approx(ctx["phase_ms"][p], rel=1e-12), p
    whole = sum(v for by in ms.values() for v in by.values())
    assert whole + ctx["phase_ms"]["other"] + ctx["phase_ms"]["idle"] == pytest.approx(
        device["window_s"] * 1e3 / ctx["steps"], rel=1e-12)
    # the while's self time (its body ops cover 2-65 and 70-75) and the slice
    assert ms["forward"]["layer_scan"] == pytest.approx((100 - 63 - 5 + 5) * 1e-6)
    assert ms["forward"]["moe_experts"] == pytest.approx(25e-6)
    assert ms["forward"]["unscoped"] == pytest.approx(4e-6)
    assert ms["remat_recompute"] == pytest.approx({"attention": 10e-6})
    assert ms["optimizer_update"] == pytest.approx(
        {"adamw": 10e-6, "grad_norm": 2e-6, "anomaly_guard": 8e-6, "unscoped": 2e-6})
    got = {name: bench.metric_reader(name).read(ctx) for name in SCOPE_READERS}
    assert got == pytest.approx({
        "attention_ms.train": 42e-6, "router_ms.train": 6e-6, "moe_dispatch_ms.train": 6e-6,
        "expert_ffn_ms.train": 33e-6, "moe_combine_ms.train": 6e-6,
        "head_loss_ms.train": 16e-6, "anomaly_guard_ms.train": 8e-6})


def test_one_walk_for_all_readers(monkeypatch):
    calls = []
    walk = layers.layer_family_ns
    monkeypatch.setattr(layers, "layer_family_ns", lambda *a: calls.append(1) or walk(*a))
    ctx = ctx_of(step_trace())
    for name in SCOPE_READERS:
        assert bench.metric_reader(name).read(ctx) > 0
    assert len(calls) == 1


@pytest.mark.parametrize("lost", ["moe_router", "moe_experts", "attention", "anomaly_guard"])
def test_a_layer_that_reads_nothing_stops_the_run(lost):
    step = [(f, p.replace(f"/{lost}/", "/renamed/"), s, e) for f, p, s, e in STEP]
    with pytest.raises(RuntimeError, match=f"in layer {lost};"):
        layers.layer_ms(ctx_of(step_trace(step)))


def test_a_dense_configuration_needs_no_moe_layer():
    step = [row for row in STEP if "/moe_" not in row[1]]
    ctx = ctx_of(step_trace(step), num_experts=0)
    assert bench.metric_reader("attention_ms.train").read(ctx) == pytest.approx(42e-6)
    assert bench.metric_reader("router_ms.train").read(ctx) is None


def test_a_program_that_names_no_layer_reads_nothing():
    """A program without the scopes (the commit before them) gives no
    value and does not raise."""
    step = [(f, "jit(step)/jvp()/while/body/closed_call/mul:" if p else p, s, e)
            for f, p, s, e in STEP]
    ctx = ctx_of(step_trace(step))
    assert layers.layer_ms(ctx) is None
    assert all(bench.metric_reader(n).read(ctx) is None for n in SCOPE_READERS)
    assert bench.metric_reader("host_serial_ms.train").read(ctx) is None


def test_host_serial_counts_the_host_spans_but_not_the_wait():
    spans = []
    for k in range(2):
        t = 200.0 * k
        spans += [Span("bench.step_fn", t + 5, 190), Span("repro.train.step", t + 10, 180),
                  Span("repro.train.input", t + 10, 10), Span("repro.train.launch", t + 20, 10),
                  Span("repro.train.wait", t + 30, 140),
                  Span("repro.train.readback", t + 170, 5),
                  Span("repro.train.callbacks", t + 175, 1)]
    spans.append(Span("repro.train.input", 450.0, 10))  # after the window
    tr = step_trace(spans=sorted(spans, key=lambda s: s.start_ns))
    assert bench.metric_reader("host_serial_ms.train").read(ctx_of(tr)) == pytest.approx(
        (10 + 10 + 5 + 1) * 1e-6)


def test_trainer_run_writes_its_spans_in_order(tmp_path):
    """``Trainer.run(2)`` under the profiler: two ``repro.train.step``
    spans, each enclosing input, launch, wait, readback and callbacks, in
    that order, on the trace's clock."""
    import jax

    from repro.config import ModelConfig, TrainConfig
    from repro.data.pipeline import make_train_iter
    from repro.train.trainer import Trainer

    cfg = ModelConfig(name="tiny", family="dense", num_layers=1, d_model=32, num_heads=2,
                      num_kv_heads=2, d_ff=64, vocab_size=128, vocab_divisor=64)
    tcfg = TrainConfig(global_batch=2, seq_len=8, seed=3)
    tr = Trainer(cfg, tcfg, data_iter=make_train_iter(cfg.vocab_size, 8, 2, seed=3))
    tr.run(1, callbacks=[])  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        tr.run(2, callbacks=[])
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = trace.load(path).spans
    steps = [s for s in spans if s.name == "repro.train.step"]
    assert len(steps) == 2
    parts = ["repro.train.input", "repro.train.launch", "repro.train.wait",
             "repro.train.readback", "repro.train.callbacks"]
    for st in steps:
        inside = [s for s in spans if s.name != "repro.train.step"
                  and st.start_ns <= s.start_ns and s.end_ns <= st.end_ns]
        assert [s.name for s in inside] == parts
        assert all(a.end_ns <= b.start_ns for a, b in zip(inside, inside[1:]))
