#!/usr/bin/env python3
"""Upper readings of a training cell's compared numbers, on the chip at
the cell's own size: the control (the reference in the program's place,
computed with float8 operands, one precision below the configuration's
bfloat16) and the fault of half of each row left out of the loss, planted
in the reference put in the program's place. The benchmark's own runs
never run this; the limits in ``limits/<cell>.json`` are set from what it
prints and from the program's sound runs.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 11 12 13

Per seed, the reference in float32 and each variant follow the same first
three steps; the variant's numbers are read as a run's are. One JSON line
per seed and variant.
"""
import argparse
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def train_controls(cell, seeds):
    from benchmarks.chip import bench, harness, reference
    from benchmarks.chip.kinds import train

    _, moe_cfg = bench.model_config(cell.config)
    d = bench.dims(cell.config, moe_cfg.padded_vocab)
    tc = cell.config["train"]
    for seed in seeds:
        feed = train.batches(cell.traffic, d.vocab, seed)
        batches = [next(feed) for _ in range(3)]
        t0 = time.time()
        ref = reference.train_steps(seed, d, batches, tc)
        keep = harness.kept_leaves(ref["grad_norms"])
        variants = {
            "fp8_control": dict(rounding="fp8"),
            "half_batch_fault": dict(loss_tokens=cell.traffic["seq"] // 2),
        }
        for name, kw in variants.items():
            got = reference.train_steps(seed, d, batches, tc, **kw)
            print(json.dumps({
                "seed": seed, "variant": name,
                "loss_gap": max(abs(a - b) for a, b in zip(got["losses"], ref["losses"])),
                "grad_gap": harness.worst_leaf_gap(got["grad_norms"], ref["grad_norms"], keep),
                "update_gap": harness.worst_leaf_gap(got["delta_norms"], ref["delta_norms"], keep),
                "left_out": sorted(set(ref["grad_norms"]) - set(keep)),
                "seconds": time.time() - t0,
            }), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from benchmarks.chip import bench, harness

    cell = bench.load_cell(args.workload)
    harness.devices(cell.chips)
    harness.enable_cache()
    if cell.traffic["kind"] != "train":
        raise SystemExit(f"control.py reads training cells only, not {cell.traffic['kind']!r}")
    train_controls(cell, args.seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
