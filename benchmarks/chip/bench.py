"""Cells, configurations and traffic mixes, found by the names that
``BENCHMARK.json`` gives them.

* a configuration is ``configs/<config>.json``: the published sizes, what
  was cut and why, the upcycling recipe and the run flags;
* a traffic mix is ``traffic/<traffic>.json``: data only (lengths, sizes,
  rates), whose ``kind`` names the module ``kinds/<kind>.py`` that
  generates the load from it and runs the cell;
* a per-layer metric is read by ``metrics/<metric>.py``;
* a cell's correctness limits are ``limits/<cell>.json``.

Adding any of these is adding a file and an entry; no code here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]  # metrics this cell reports untraced
    per_layer: List[Dict[str, Any]]  # metrics this cell reports traced
    run_seconds: int
    here: Path = HERE  # where its traffic kind and metric readers are found


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = CHECKOUT, here: Path = HERE) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfile = root / configs[w["config"]]["file"]
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if m["moves"] in reported and _applies(m, name)]
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]), config=load_json(cfile),
        traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(here / "limits" / f"{name}.json"),
        end_to_end=e2e, per_layer=layer, run_seconds=int(spec["run_seconds"]),
        here=here,
    )


def _module(directory: Path, name: str, what: str) -> ModuleType:
    path = directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no module for {what} {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{directory.name}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: Path = HERE) -> ModuleType:
    """The module ``metrics/<name>.py``; it defines ``read(ctx)`` that
    returns the metric's value, or None where it finds nothing to read."""
    return _module(here / "metrics", name, "per-layer metric")


def traffic_kind(kind: str, here: Path = HERE) -> ModuleType:
    """The module ``kinds/<kind>.py``; it defines ``run(cell, seed,
    seconds, trace_on, t_start, devs)`` that returns a ``harness.Run``."""
    return _module(here / "kinds", kind, "traffic kind")


def model_config(config: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: the base
    architecture with depth and vocabulary as the file states and the
    upcycling recipe applied. Every width must equal the base's, so a
    configuration can never run at widths it does not state."""
    from repro.config import MoEConfig, get_config
    from repro.core.upcycle import upcycle_config

    base = get_config(config["base_arch"])
    widths = {
        "hidden_size": base.d_model, "intermediate_size": base.d_ff,
        "num_attention_heads": base.num_heads,
        "num_key_value_heads": base.num_kv_heads, "head_dim": base.head_dim_,
    }
    for k, v in widths.items():
        if config[k] != v:
            raise ValueError(f"{config['base_arch']}: {k} {config[k]} in the "
                             f"configuration file, {v} in the program")
    run = config["run"]
    dense = base.replace(
        num_layers=config["num_hidden_layers"], vocab_size=config["vocab_size"],
        qkv_bias=bool(config.get("use_qkv_bias", config.get("attention_bias", False))),
        remat=run.get("remat", base.remat),
        train_microbatches=run.get("train_microbatches", base.train_microbatches),
        dtype=run["dtype"],
    )
    up = config["upcycle"]
    moe = MoEConfig(
        num_experts=up["num_experts"], top_k=up["top_k"],
        capacity_factor=up["capacity_factor"], router_type=up["router_type"],
        dispatcher=up["dispatcher"],
    )
    return dense, upcycle_config(dense, moe)


def dims(config: Dict[str, Any], padded_vocab: int):
    from .gen import Dims

    return Dims.from_config(config, padded_vocab)
