"""The reduction from a profiler trace to device numbers, on a small trace
recorded on a TPU v5e (a one-layer upcycled training step with the
flash-attention and grouped-GEMM kernels, then a paged decode step, each
twice, under harness-style ``bench.*`` spans), on hand-made ops and on a
hand-made trace file."""
import os
import re
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


# -- the op-name path, self time, train-step phases, program spans ----------


def witness_space():
    """The fixture decoded by protobuf's own parser from the fields of the
    public xplane.proto that trace.py reads (the two maps as their
    wire-identical repeated entries): a second decoder to hold trace.py's
    against."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="xplane_witness.proto",
                                            package="witness", syntax="proto3")
    schema = {
        "XStat": [("metadata_id", 1, "int64"), ("uint64_value", 3, "uint64"),
                  ("int64_value", 4, "int64"), ("str_value", 5, "string"),
                  ("ref_value", 7, "uint64")],
        "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
                   ("duration_ps", 3, "int64")],
        "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
                  ("events", 4, "*XEvent")],
        "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                           ("stats", 5, "*XStat")],
        "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
        "EventEntry": [("key", 1, "int64"), ("value", 2, ".XEventMetadata")],
        "StatEntry": [("key", 1, "int64"), ("value", 2, ".XStatMetadata")],
        "XPlane": [("name", 2, "string"), ("lines", 3, "*XLine"),
                   ("event_metadata", 4, "*EventEntry"), ("stat_metadata", 5, "*StatEntry")],
        "XSpace": [("planes", 1, "*XPlane")],
    }
    for msg, fields in schema.items():
        m = fd.message_type.add(name=msg)
        for name, number, kind in fields:
            f = m.field.add(name=name, number=number, label=F.LABEL_OPTIONAL)
            if kind[0] in "*.":
                f.type, f.type_name = F.TYPE_MESSAGE, ".witness." + kind[1:]
                if kind[0] == "*":
                    f.label = F.LABEL_REPEATED
            else:
                f.type = getattr(F, "TYPE_" + kind.upper())
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    space = message_factory.GetMessageClass(pool.FindMessageTypeByName("witness.XSpace"))()
    space.ParseFromString(SMALL.read_bytes())
    return space


def witness_ops():
    """(text, start, duration, tf_op, program id) of each ``XLA Ops`` event
    of the fixture's device, sorted by start as trace.load sorts."""
    (plane,) = [p for p in witness_space().planes if p.name == "/device:TPU:0"]
    stat = {e.key: e.value.name for e in plane.stat_metadata}
    meta = {}
    for e in plane.event_metadata:
        md, scope, program = e.value, "", None
        for s in md.stats:
            if stat[s.metadata_id] == "tf_op":
                scope = s.str_value or stat.get(s.ref_value, "")
            elif stat[s.metadata_id] == "program_id":
                program = s.uint64_value or s.int64_value
        meta[e.key] = (md.name, scope, program)
    (line,) = [ln for ln in plane.lines if ln.name == "XLA Ops"]
    out = [(meta[e.metadata_id][0], float(line.timestamp_ns + e.offset_ps // 1000),
            float(e.duration_ps // 1000)) + meta[e.metadata_id][1:] for e in line.events]
    return sorted(out, key=lambda o: o[1])


def test_decoder_matches_profile_data_and_a_second_decoder(small):
    ops = small.devices["/device:TPU:0"]
    assert [(o.text, o.start_ns, o.dur_ns) for o in ops] == sorted(
        [(n, float(s), float(d)) for n, s, d in raw_ops()], key=lambda o: o[1])
    assert [(o.text, o.start_ns, o.dur_ns, o.scope) for o in ops] == [w[:4] for w in witness_ops()]


def test_every_op_of_the_step_has_its_op_name_path(small):
    """The step's ops are those of its program; every one of them whose
    metadata carries a path has it, and those that carry none (async copy
    halves, buffer allocations) hold under a tenth of its device time."""
    w = witness_ops()
    step = {p for _, _, _, scope, p in w if scope.startswith("jit(step)/")}
    assert len(step) == 1
    ops = [o for o, x in zip(small.devices["/device:TPU:0"], w) if x[4] in step]
    scoped = [o for o in ops if o.scope]
    assert len(scoped) == 638 and all(o.scope.startswith("jit(step)/") for o in scoped)
    lo, hi = small.window()
    self_time = {id(o): t for o, t in trace.self_ns(small.devices["/device:TPU:0"], lo, hi)}
    bare = sum(self_time[id(o)] for o in ops if not o.scope)
    assert 0 < bare < 0.1 * sum(self_time[id(o)] for o in ops)


@pytest.mark.parametrize("name,marker,phase", [
    ("grouped_gemm.46", "jit(step)/jvp()/", "forward"),
    ("grouped_gemm.43", "jit(step)/transpose(jvp())/", "backward"),
    ("grouped_gemm.41", "/rematted_computation/jit(grouped_gemm)/", "remat_recompute"),
])
def test_known_op_name_paths(small, name, marker, phase):
    ops = [o for o in small.devices["/device:TPU:0"] if o.name == name and o.scope]
    assert ops and all(marker in o.scope and trace.phase(o) == phase for o in ops)


def test_self_time_of_enclosing_ops(small):
    """The fixture's 8 ``while`` events enclose the ops of their bodies:
    each keeps only its loop overhead, and self times sum to busy time."""
    ops = small.devices["/device:TPU:0"]
    lo, hi = small.window()
    whiles = [(o, t) for o, t in trace.self_ns(ops, lo, hi) if o.family == "while"]
    assert len(whiles) == 8
    for o, t in whiles:
        inside = [c for c in ops if c is not o and o.start_ns <= c.start_ns < o.end_ns]
        assert inside and 0 <= t < 0.2 * o.dur_ns
    busy = trace.busy_ns(ops, lo, hi)
    assert sum(t for _, t in trace.self_ns(ops, lo, hi)) == pytest.approx(busy, rel=1e-9)
    assert sum(s for _, s in trace.top_ops(small, lo, hi, top=1000)) <= busy * 1e-9 * (1 + 1e-9)


def test_self_time_nested_and_clipped():
    """while 0-100 holding a fusion 10-30 and a call 40-90 that holds a
    fusion 50-60; a copy 100-110 after it."""
    dev = ops(("while.1", 0, 100), ("fusion.2", 10, 30), ("call.3", 40, 90),
              ("fusion.4", 50, 60), ("copy.5", 100, 110))
    got = {o.name: t for o, t in trace.self_ns(dev, 0, 110)}
    assert got == {"while.1": 30, "fusion.2": 20, "call.3": 40, "fusion.4": 10, "copy.5": 10}
    got = {o.name: t for o, t in trace.self_ns(dev, 20, 55)}
    assert got == {"while.1": 10, "fusion.2": 10, "call.3": 10, "fusion.4": 5}
    tr = Trace({"/device:TPU:0": dev}, [])
    assert dict(trace.top_ops(tr, 0, 110)) == pytest.approx(
        {"while": 30e-9, "fusion": 30e-9, "call": 40e-9, "copy": 10e-9})


@pytest.mark.parametrize("path,phase", [
    ("jit(step)/jvp()/while/body/closed_call/dot_general", "forward"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/dot_general", "backward"),
    ("jit(step)/transpose(jvp())/while/body/checkpoint/rematted_computation/sort",
     "remat_recompute"),
    ("jit(step)/add", "optimizer_update"),
    ("jit(step)", "optimizer_update"),
    ("jit(<lambda>)/jvp()/dot_general", "other"),
    ("jit(stepper)/add", "other"),
    ("", "other"),
])
def test_phase_of_an_op_name_path(path, phase):
    assert trace.phase(Op("fusion.1", "", 0, 1, path)) == phase


def test_phases_of_the_fixture_step(small):
    """Self time of the two traced steps by phase (ms): every phase
    non-zero, and with ``other`` they make up the busy time."""
    lo, hi = small.window()
    got = {p: sum(f.values()) for p, f in trace.phase_family_ns(small, lo, hi).items()}
    assert set(got) == set(trace.PHASES) | {"other"}
    assert {k: got[k] * 1e-6 for k in trace.PHASES} == pytest.approx(
        {"forward": 2.0456, "backward": 3.8836, "remat_recompute": 1.8890,
         "optimizer_update": 7.2006}, abs=1e-4)
    assert sum(got.values()) == pytest.approx(
        trace.busy_ns(small.devices["/device:TPU:0"], lo, hi), rel=1e-9)


def train_ctx(tr, remat="full", steps=2):
    lo, hi = tr.window()
    device = {"busy_s": trace.mean_busy_ns(tr, lo, hi) * 1e-9, "window_s": (hi - lo) * 1e-9}
    ctx = {"kind": "train", "trace": tr, "lo": lo, "hi": hi, "steps": steps, "remat": remat}
    return ctx, device


def phase_readers():
    from benchmarks.chip import bench

    return {p: bench.metric_reader(f"{p}_ms.train") for p in trace.PHASES}


def test_phases_other_and_idle_make_up_the_window(small):
    from benchmarks.chip.kinds import train

    ctx, device = train_ctx(small)
    ctx = train.read_trace(ctx, device)
    parts = ctx["phase_ms"]
    assert list(parts) == list(trace.PHASES) + ["other", "idle"]
    assert parts["idle"] > 0
    assert sum(parts.values()) == pytest.approx(device["window_s"] * 1e3 / 2, rel=1e-9)
    assert {p: r.read(ctx) for p, r in phase_readers().items()} == {
        p: parts[p] for p in trace.PHASES}


STEP_OPS = {"forward": "jit(step)/jvp(f)/dot_general",
            "backward": "jit(step)/transpose(jvp(f))/dot_general",
            "remat_recompute": "jit(step)/transpose(jvp(f))/rematted_computation/dot_general",
            "optimizer_update": "jit(step)/add"}


@pytest.mark.parametrize("scopes,remat,empty", [
    (STEP_OPS, "full", None),
    ({p: s for p, s in STEP_OPS.items() if p != "remat_recompute"}, "none", None),
    ({p: s for p, s in STEP_OPS.items() if p != "remat_recompute"}, "full", "remat_recompute"),
    ({p: s for p, s in STEP_OPS.items() if p != "optimizer_update"}, "full", "optimizer_update"),
    ({p: s.replace("jit(step)", "jit(train_step)") for p, s in STEP_OPS.items()}, "full",
     "forward, backward, remat_recompute, optimizer_update"),
    ({p: "wrapped/" + s for p, s in STEP_OPS.items()}, "none",
     "forward, backward, optimizer_update"),
])
def test_phase_readers_raise_where_the_step_is_out_of_sight(scopes, remat, empty):
    """Hand-made ops of a step: a renamed or wrapped step module, or a
    phase with no op, stops the run instead of dropping the metrics; only
    the recompute may be absent, and only where the configuration does not
    remat, and then its reader gives nothing."""
    from benchmarks.chip.kinds import train

    dev = [Op(f"fusion.{i}", "", 10 * i, 5, s) for i, s in enumerate(scopes.values())]
    ctx, device = train_ctx(Trace({"/device:TPU:0": dev}, []), remat, steps=1)
    if empty is not None:
        with pytest.raises(RuntimeError, match=f"in phase {re.escape(empty)} "):
            train.read_trace(ctx, device)
        return
    got = {p: r.read(train.read_trace(ctx, device)) for p, r in phase_readers().items()}
    assert {p for p, v in got.items() if v is None} == set(trace.PHASES) - set(scopes)
    assert {p: got[p] for p in scopes} == pytest.approx({p: 5e-6 for p in scopes}, rel=1e-12)


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def pb(*fields):
    """A protobuf message of (number, int | str | bytes) fields."""
    out = b""
    for number, v in fields:
        if isinstance(v, int):
            out += _varint(number << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(number << 3 | 2) + _varint(len(v)) + v
    return out


def test_program_spans_and_referenced_stats_are_read(tmp_path):
    """A hand-made trace: a ``repro.*`` span is kept beside a ``bench.*``
    one and another host event is not; a ``tf_op`` given by reference
    resolves; the async line is left out."""
    host = pb((2, "/host:CPU"),
              (3, pb((2, "main"), (3, 1000),
                     (4, pb((1, 1), (2, 0), (3, 100_000))),
                     (4, pb((1, 2), (2, 20_000), (3, 60_000))),
                     (4, pb((1, 3), (2, 30_000), (3, 1_000))))),
              (4, pb((1, 1), (2, pb((1, 1), (2, "bench.window"))))),
              (4, pb((1, 2), (2, pb((1, 2), (2, "repro.train.step"))))),
              (4, pb((1, 3), (2, pb((1, 3), (2, "PjitFunction(step)"))))))
    tf_op = lambda value: (5, pb((1, 1), value))  # noqa: E731  XEventMetadata.stats
    device = pb((2, "/device:TPU:0"),
                (3, pb((2, "XLA Ops"), (3, 1000),
                       (4, pb((1, 5), (2, 0), (3, 10_000))),
                       (4, pb((1, 6), (2, 20_000), (3, 10_500))))),
                (3, pb((2, "Async XLA Ops"), (3, 1000), (4, pb((1, 5), (2, 0), (3, 90_000))))),
                (4, pb((1, 5), (2, pb((1, 5), (2, "%fusion.1 = f32[] fusion()"), tf_op((7, 2)))))),
                (4, pb((1, 6), (2, pb((1, 6), (2, "%copy.2 = f32[] copy()"),
                                      tf_op((5, "jit(step)/transpose(jvp())/copy")))))),
                (5, pb((1, 1), (2, pb((1, 1), (2, "tf_op"))))),
                (5, pb((1, 2), (2, pb((1, 2), (2, "jit(step)/jvp()/dot_general"))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(pb((1, host), (1, device)))
    tr = trace.load(str(path))
    assert [(s.name, s.start_ns, s.dur_ns) for s in tr.spans] == [
        ("bench.window", 1000.0, 100.0), ("repro.train.step", 1020.0, 60.0)]
    assert [(o.name, o.start_ns, o.dur_ns, o.scope) for o in tr.devices["/device:TPU:0"]] == [
        ("fusion.1", 1000.0, 10.0, "jit(step)/jvp()/dot_general"),
        ("copy.2", 1020.0, 10.0, "jit(step)/transpose(jvp())/copy")]
    assert [trace.phase(o) for o in tr.devices["/device:TPU:0"]] == ["forward", "backward"]


def test_a_program_span_wins_a_gap_over_an_enclosing_harness_span():
    dev = ops(("fusion.1", 0, 10), ("fusion.2", 20, 30))
    tr = Trace({"/device:TPU:0": dev},
               [Span("bench.window", 0, 100), Span("repro.train.step", 20, 60)])
    got = dict(trace.attribute_gaps(tr, 0, 100))
    assert got == pytest.approx({"bench.window": 10e-9, "repro.train.step": 70e-9})
