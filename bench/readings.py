#!/usr/bin/env python3
"""Readings for setting a cell's correctness limits, many seeds in one
process (set-up is paid once per seed from a warm compilation cache).

    python bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--controls bf16,half_batch] [--fault serve.half_batch] [--out FILE]

For each seed it runs the cell's window and check, and prints one JSON
line: the numbers compared (``readings``) and, for each control or
planted fault named, the same numbers read from the lower-precision
reference (``control.<mode>.*``) or the faulty one.  ``--fault`` plants
one of ``benchlib.faults`` under the timed path at the cell's own size:
its runs must come out not correct.  A limit is set
between the largest reading of the program over a dozen seeds or more
and the smallest reading of the control over three or more.  The
benchmark's own runs never run a control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from benchlib import spec

    cell = spec.load_cell(args.workload)
    sys.path.insert(0, os.path.join(bench_run.ROOT, "src"))
    import jax

    if jax.default_backend() != "tpu":
        print(f"error: JAX finds no TPU (backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    controls = tuple(c for c in args.controls.split(",") if c)
    if args.fault:
        from benchlib import faults

        faults.plant(args.fault)
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            r = bench_run.measure(cell, seed, args.seconds, False,
                                  controls=controls)
            w = {k: v for k, v in r["window"].items() if k != "t_start"}
            line = json.dumps({"workload": cell.name, "seed": seed,
                               "fault": args.fault,
                               "correct": r["result"]["correct"],
                               "readings": r["readings"], "window": w,
                               "device": r["result"]["device"]})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
