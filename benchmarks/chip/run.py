#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for. The last line of standard output is the result, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with the reference beside its limit. The same numbers end standard error.
With no TPU, or fewer chips than the cell asks for, it exits 3 and prints
no result.
"""
import time

T_START = time.time()  # set-up is timed from the start of the process

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from benchmarks.chip import bench, harness

    cell = bench.load_cell(args.workload)
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"run.py: the program is not in this checkout ({CHECKOUT / 'src'}): {e}",
              file=sys.stderr)
        return 2
    try:
        devs = harness.devices(cell.chips)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    harness.enable_cache()
    result = harness.execute(cell, args.seed, args.seconds, bool(args.trace),
                             T_START, devs)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
