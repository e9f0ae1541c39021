"""Serving's operation and byte counts against hand counts, and the
serving readers on a hand-made trace and on the recorded one: programs
told apart by how often they ran, ms per run, roofline shares and the
whole-step share."""
import os
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from benchmarks.chip import bench, flops, flops_serve, serve_trace, trace  # noqa: E402
from benchmarks.chip.gen import Dims  # noqa: E402
from benchmarks.chip.serve_trace import ModuleRun  # noqa: E402
from benchmarks.chip.trace import Op, Trace  # noqa: E402

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"
PK = flops.peaks("TPU v5 lite")


def qwen():
    return Dims.from_config(bench.load_json(bench.HERE / "configs" / "qwen2.5-14b-e8t2.json"),
                            padded_vocab=38912)


def tiny():
    return Dims(d_model=8, d_ff=16, num_heads=4, num_kv_heads=2, head_dim=2, num_layers=1,
                vocab=10, padded_vocab=16, num_experts=4, top_k=2, norm="rmsnorm",
                norm_eps=1e-5, rope_theta=1e4, qkv_bias=True)


def test_paged_attention_counts():
    # rows of 5 and 16 keys, pages of 4: 2 + 4 pages; KV 2 heads of 2, bf16
    f = flops_serve.paged_attention(tiny(), [5, 16], page_size=4)
    assert f["flops"] == 4 * 4 * 2 * (5 + 16)
    assert f["bytes"] == 2 * (2 + 4) * 4 * 2 * 2 * 2 + 2 * 2 * 4 * 2 * 2
    # the cell's widths: a 1,024-token context reads 64 pages of 16 x 8 KV heads x 128
    g = flops_serve.paged_attention(qwen(), [1024], page_size=16)
    assert g["bytes"] == 2 * 64 * 16 * 8 * 128 * 2 + 2 * 40 * 128 * 2
    assert g["flops"] / g["bytes"] < 6  # far below the chip's ridge: bandwidth-bound


def test_forward_counts():
    d = tiny()
    D, H, KV, hd, F = 8, 4, 2, 2, 16
    per = 2 * D * H * hd + 4 * D * KV * hd + 2 * H * hd * D + 2 * D * 4 + 2 * 3 * 2 * D * F
    assert flops_serve.layer_token_flops(d) == per
    # a chunk of 3 tokens at positions 4, 5, 6 attends to 5 + 6 + 7 keys
    assert flops_serve.chunk_flops(d, 4, 3, False) == 3 * per + 4 * H * hd * 18
    assert flops_serve.chunk_flops(d, 4, 3, True) == 3 * per + 4 * H * hd * 18 + 2 * D * 10
    assert flops_serve.decode_flops(d, [7, 1]) == 2 * (per + 2 * D * 10) + 4 * H * hd * 8


def test_expert_gemm_counts():
    d = tiny()
    one = flops_serve.expert_gemm(d, 1)  # 2 rows: at most 2 experts read
    assert one["flops"] == 6 * 2 * 8 * 16
    assert one["bytes"] == 2 * 3 * 8 * 16 * 2 + 2 * 2 * 8 * 2
    assert flops_serve.expert_gemm(d, 10)["bytes"] == 4 * 3 * 8 * 16 * 2 + 2 * 20 * 8 * 2


def op(name, start, dur):
    return Op(name, f"%{name} = f32[] x()", start, dur)


def hand_trace():
    """Two decode runs (paged attention inside) and one prefill-chunk run of
    the engine's lambdas, an argmax after each decode, under a 1 ms
    window."""
    ops = [op("fusion.1", 0, 100_000), op("paged_attention.2", 100_000, 50_000),
           op("grouped_gemm.3", 150_000, 100_000),
           op("argmax.1", 260_000, 10_000),
           op("fusion.9", 300_000, 200_000), op("grouped_gemm.4", 500_000, 150_000),
           op("fusion.1", 700_000, 100_000), op("paged_attention.2", 800_000, 50_000),
           op("grouped_gemm.3", 850_000, 100_000), op("argmax.1", 960_000, 10_000)]
    mods = [ModuleRun("jit__lambda(111)", 0, 250_000), ModuleRun("jit_argmax(5)", 260_000, 10_000),
            ModuleRun("jit__lambda(222)", 300_000, 350_000),
            ModuleRun("jit__lambda(111)", 700_000, 250_000),
            ModuleRun("jit_argmax(5)", 960_000, 10_000)]
    return Trace({"/device:TPU:0": ops}, []), {"/device:TPU:0": mods}


def ctx_for(tr, mods, **over):
    d = tiny()
    ctx = {"kind": "serve", "trace": tr, "modules": mods, "lo": 0.0, "hi": 1_000_000.0,
           "chips": 1, "peaks": PK, "dims": d, "page_size": 4, "steps": 3,
           "chunks": [(0, 5, True)], "decodes": [[6], [7]], "chunk_steps": 1, **over}
    from benchmarks.chip.kinds import serve

    device = {"busy_s": trace.mean_busy_ns(tr, 0, 1e6) * 1e-9, "window_s": 1e-3}
    return serve.read_trace(ctx, device)


def read(name, ctx):
    return bench.metric_reader(name).read(ctx)


def test_serving_readers_on_a_hand_made_trace():
    ctx = ctx_for(*hand_trace())
    progs = ctx["programs"]
    assert progs["decode"]["runs"] == 2 and progs["prefill"]["runs"] == 1
    assert read("decode_ms.serve", ctx) == pytest.approx(0.25)
    assert read("prefill_ms.serve", ctx) == pytest.approx(0.35)
    assert read("idle_share.serve", ctx) == pytest.approx(100 * (1 - 0.87))
    d = tiny()
    gemm = sum(flops.least_seconds(flops_serve.expert_gemm(d, n), PK) for n in (5, 1, 1))
    assert read("expert_gemm_roofline.serve", ctx) == pytest.approx(100 * gemm / 350e-6)
    attn = sum(flops.least_seconds(flops_serve.paged_attention(d, [n], 4), PK) for n in (6, 7))
    assert read("paged_attention_roofline.serve", ctx) == pytest.approx(100 * attn / 100e-6)
    work = flops_serve.chunk_flops(d, 0, 5, True) + flops_serve.decode_flops(d, [6]) \
        + flops_serve.decode_flops(d, [7])
    assert read("mfu.serve", ctx) == pytest.approx(100 * work / (1e-3 * PK["bf16_flops_per_s"]))


def test_readers_find_nothing_outside_serving():
    tr, mods = hand_trace()
    idle = {**ctx_for(tr, mods), "chunks": [], "decodes": [], "chunk_steps": 0}
    from benchmarks.chip.kinds import serve

    ctx = serve.read_trace(idle, {"busy_s": 0.0, "window_s": 1e-3})
    assert ctx["programs"] == {}
    assert read("decode_ms.serve", ctx) is None and read("prefill_ms.serve", ctx) is None
    train_ctx = {"kind": "train", "trace": tr, "lo": 0.0, "hi": 1e6}
    for name in ("mfu.serve", "idle_share.serve", "expert_gemm_roofline.serve",
                 "paged_attention_roofline.serve"):
        assert read(name, train_ctx) is None


def test_programs_are_found_by_their_runs_not_their_names():
    """Jits and kernels named otherwise still read the same."""
    tr, mods = hand_trace()
    ren = {"jit__lambda(111)": "jit_decode(9)", "jit__lambda(222)": "jit_chunk(8)"}
    mods = {k: [ModuleRun(ren.get(r.name, r.name), r.start_ns, r.dur_ns) for r in v]
            for k, v in mods.items()}
    ops = [Op(o.name.replace("paged_attention", "ragged_attn"),
              o.text.replace("paged_attention", "ragged_attn"), o.start_ns, o.dur_ns)
           for o in tr.devices["/device:TPU:0"]]
    progs = ctx_for(Trace({"/device:TPU:0": ops}, []), mods)["programs"]
    assert progs["decode"]["runs"] == 2 and progs["prefill"]["runs"] == 1
    assert progs["decode"]["self_ns"] == pytest.approx(500_000)
    assert progs["prefill"]["self_ns"] == pytest.approx(350_000)


@pytest.mark.parametrize("lost", ["modules", "decode", "chunk"])
def test_a_program_that_ran_and_is_not_in_the_trace_raises(lost):
    """Where the window ran decode steps or chunks and no program in the
    trace ran as often, the run stops instead of dropping the metrics."""
    tr, mods = hand_trace()
    extra = {}
    if lost == "modules":
        mods = {}
    elif lost == "decode":
        extra = {"decodes": [[6], [7], [8]]}
    else:
        mods = {k: [r for r in v if r.name != "jit__lambda(222)"] for k, v in mods.items()}
    with pytest.raises(RuntimeError, match="no program in the trace ran once per"):
        ctx_for(tr, mods, **extra)


def test_a_program_faster_than_its_work_is_not_taken():
    """At the cell's widths a decode step of one row reads two experts,
    0.85 GB a layer: a program of 0.25 ms a run that ran as often is not
    the decode program, and the run stops."""
    tr, mods = hand_trace()
    with pytest.raises(RuntimeError, match="it is not the decode program"):
        ctx_for(tr, mods, dims=qwen())


def test_recorded_trace_modules():
    """The recorded trace's decode step runs as a ``jit__lambda`` module
    with the paged-attention kernel; the train step is no engine program."""
    tr = trace.load(str(SMALL))
    mods = serve_trace.load_modules(str(SMALL))
    names = {r.name.split("(")[0] for r in mods["/device:TPU:0"]}
    assert {"jit_step", "jit__lambda"} <= names
    lo, hi = tr.window()
    progs = serve_trace.programs(tr, mods, lo, hi, decodes=2, chunk_calls=(0,))
    assert set(progs) == {"decode"} and progs["decode"]["runs"] == 2
    assert progs["decode"]["families"]["paged_attention"] > 0
    assert 0 < progs["decode"]["self_ns"] < hi - lo
