#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload lit_vga.frames --seed 7 --seconds 10 \
        --trace 0

From the root of a checkout.  The cell, its configuration, traffic mix and
metrics are named in ``BENCHMARK.json``; ``bench/harness.py`` says how a run
goes.  With ``--trace 1`` the window runs under the JAX profiler and the
per-layer metrics are printed instead of the end-to-end ones.  Exits 3,
printing no result, when JAX finds no TPU or fewer chips than the cell asks
for, and 2 when the program under test is not beside the benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reduction)")
    args = ap.parse_args(argv)
    # JAX's persistent compilation cache lives at a fixed path inside the
    # checkout; the program's own cache helper takes it from this variable.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        import repro.core.executor  # noqa: F401
    except ImportError as e:
        print(f"bench: the program under test is missing ({e}); run from "
              "the root of a full checkout", file=sys.stderr)
        return 2
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Cache every program, however quickly it compiled, so that a second
    # run of a cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from bench import harness

    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
