"""The harness is driven by data: a cell, its configuration, traffic mix,
traffic kind, limits and per-layer metric readers are found by the names
BENCHMARK.json gives them. A new traffic kind, a mix of it and a new
metric, added as files in directories of their own, are found and run
without a line of the harness changing. And with no chip, a run exits
non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from benchmarks.chip import bench, harness  # noqa: E402
from benchmarks.chip.kinds import train  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]


def test_every_cell_resolves():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = bench.load_cell(w["name"])
        names = {m["name"] for m in cell.per_layer}
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and names
        for m in names:
            assert callable(bench.metric_reader(m).read)


DUMMY_KIND = """
from benchmarks.chip import harness


def requests(t, seed):
    return [(seed + i) % t["spread"] for i in range(t["count"])]


def run(cell, seed, seconds, trace_on, t_start, devs):
    done = requests(cell.traffic, seed)
    return harness.Run(
        e2e={"echoes_per_s": len(done) / seconds, "setup_s": 0.5},
        checks={"echo_gap": (0.0, cell.limits["echo_gap"])},
        attempted=len(done), failed=0, ctx={"kind": "echo", "memory_peak_bytes": 1})
"""


def test_new_traffic_kind_and_metric_are_found(tmp_path):
    here = tmp_path / "bench"
    shutil.copytree(bench.HERE / "configs", here / "configs")
    for sub in ("traffic", "kinds", "limits", "metrics"):
        (here / sub).mkdir()
    (here / "kinds" / "echo.py").write_text(DUMMY_KIND)
    (here / "traffic" / "echo_small.json").write_text(
        json.dumps({"kind": "echo", "count": 7, "spread": 3}))
    (here / "limits" / "echo.dummy.small.json").write_text('{"echo_gap": 0.1}')
    (here / "metrics" / "dummy_share.echo.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx['kind'] == 'echo' else None\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "echo.dummy.small", "config": "stablelm2-1.6b-e8t2",
                              "traffic": "echo_small", "chips": 1, "why": "a test"})
    spec["end_to_end"].append({"name": "echoes_per_s", "unit": "1/s", "better": "higher",
                               "bound": 0.1, "source": "host_clock",
                               "workloads": ["echo.dummy.small"]})
    spec["per_layer"].append({"name": "dummy_share.echo", "unit": "%", "better": "higher",
                              "source": "program_counter", "layer": "device",
                              "moves": "echoes_per_s", "workloads": ["echo.dummy.small"]})
    root = tmp_path / "root"
    root.mkdir()
    for c in spec["configs"]:
        c["file"] = str(here / "configs" / Path(c["file"]).name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = bench.load_cell("echo.dummy.small", root=root, here=here)
    assert cell.traffic["count"] == 7 and cell.limits == {"echo_gap": 0.1}
    assert sorted(m["name"] for m in cell.end_to_end) == ["echoes_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["dummy_share.echo"]
    assert bench.metric_reader("dummy_share.echo", here=here).read({"kind": "echo"}) == 42.0
    assert bench.traffic_kind("echo", here=here).requests(cell.traffic, 5) == [2, 0, 1, 2, 0, 1, 2]
    dev = type("Dev", (), {"platform": "tpu", "device_kind": "TPU v5 lite"})()
    out = harness.execute(cell, seed=5, seconds=2.0, trace_on=False, t_start=0.0, devs=[dev])
    assert out["correct"] and out["attempted"] == 7
    assert out["metrics"]["echoes_per_s"] == {"value": 3.5, "unit": "1/s"}
    assert list(out)[-1] == "checks"
    with pytest.raises(FileNotFoundError):
        bench.traffic_kind("no_such_kind", here=here)


def test_same_seed_same_batches():
    t = bench.load_json(bench.HERE / "traffic" / "train_s4096.json")
    t = dict(t, seq=32)
    a, b, c = (train.batches(t, 1000, s) for s in (2**40 + 1, 2**40 + 1, 2**40 + 2))
    for _ in range(3):
        x, y, z = next(a), next(b), next(c)
        assert (x["tokens"] == y["tokens"]).all() and (x["labels"] == y["labels"]).all()
        assert not (x["tokens"] == z["tokens"]).all()
        assert (x["tokens"][:, 1:] == x["labels"][:, :-1]).all()


def test_no_chip_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "train.stablelm2-e8t2.s4096",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == "", (p.returncode, p.stdout)
    assert "no TPU" in p.stderr


def test_only_the_benchmark_is_no_run(tmp_path):
    """A checkout of nothing but BENCHMARK.json and benchmarks/chip: no
    program, so no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(bench.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "train.stablelm2-e8t2.s4096",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == "", (p.returncode, p.stdout)
