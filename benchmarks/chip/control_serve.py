#!/usr/bin/env python3
"""Upper readings of a serving cell's compared numbers, on the chip at the
cell's own size and load: whole runs of the cell (``harness.execute``,
with the cell's own limits, as ``run.py`` makes them), with the float8
control or a fault in the program's place, all seeds in one process. The
benchmark's own runs never run this; the limits in ``limits/<cell>.json``
are set from what it prints, and each of its runs has to come out as not
``correct``.

    python3 benchmarks/chip/control_serve.py --workload <cell> \
        --seconds 10 --control 11 12 13 --fault 21 22 23 [--program 31 32]

* ``--control``: the float8 reference (``reference_serve``,
  ``rounding="fp8"``) in the program's place: the run's comparison takes
  its logits at the checked positions and its first choice at each
  sampled served position as what the program produced;
* ``--fault``: the program with its decode step reading a block table
  shifted by one page (each row's logical page j read from the page the
  table names for j + 1), planted under the engine's decode program;
* ``--program``: the sound program, for comparison.

One JSON line per seed and variant: ``correct``, each compared number
beside its limit, the run's end-to-end numbers.
"""
import argparse
import contextlib
import functools
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


@contextlib.contextmanager
def fp8_control():
    """The float8 control in the program's place, for kinds loaded inside
    the block."""
    from benchmarks.chip import serve_loop

    real = serve_loop.compare
    serve_loop.compare = functools.partial(real, rounding="fp8")
    try:
        yield
    finally:
        serve_loop.compare = real


@contextlib.contextmanager
def shifted_block_table():
    """The fault: every decode call of engines built inside the block reads
    (and writes) through its block table moved left by one page."""
    import jax.numpy as jnp

    import repro.serving.engine as engine

    real = engine.decode_step_paged

    def shifted(cfg, plan, params, pool, tokens, pos, page_table, active, **kw):
        pad = jnp.full_like(page_table[:, :1], -1)
        return real(cfg, plan, params, pool, tokens, pos,
                    jnp.concatenate([page_table[:, 1:], pad], axis=1), active, **kw)

    engine.decode_step_paged = shifted
    try:
        yield
    finally:
        engine.decode_step_paged = real


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, nargs="*", default=[])
    ap.add_argument("--fault", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from benchmarks.chip import bench, harness

    cell = bench.load_cell(args.workload)
    if cell.traffic["kind"] != "serve":
        raise SystemExit(f"control_serve.py reads serving cells only, not {cell.traffic['kind']!r}")
    devs = harness.devices(cell.chips)
    harness.enable_cache()
    for variant, seeds, planted in (("program", args.program, contextlib.nullcontext),
                                    ("fp8_control", args.control, fp8_control),
                                    ("fault_block_table_shift", args.fault,
                                     shifted_block_table)):
        for seed in seeds:
            t0 = time.time()
            with planted():
                out = harness.execute(cell, seed, args.seconds, False, t0, devs)
            print(json.dumps({"seed": seed, "variant": variant, "correct": out["correct"],
                              "checks": out["checks"], "metrics": out["metrics"],
                              "seconds": time.time() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
