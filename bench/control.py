#!/usr/bin/env python3
"""Readings of a cell's compared numbers on many seeds, for the program and
for its control, in one process.

    python3 bench/control.py --workload lit_vga.frames --seconds 3 \
        --seeds 11 12 13

Each seed runs the cell as ``run.py`` would, with a short window, and then
compares what the timed path produced twice: with the plain reference
(the program's reading), and the reference computed from bfloat16 inputs
with the float32 reference (the control: one precision step below what the
configuration states, in the program's place).  A limit lies between the
largest program reading and the smallest control reading.  The benchmark's
own runs never run the control.
"""
import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    for seed in args.seeds:
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               log=lambda m: None)
        ctl = res["_record"]["_check"](True)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program": {k: c["value"] for k, c in res["checks"].items()},
            "control": {k: ctl[k] for k in res["checks"]},
            "compared_elements": ctl["compared_elements"],
            "metrics": {k: m["value"] for k, m in res["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
