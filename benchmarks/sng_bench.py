"""Batched vs per-PI stream generation (BENCH_sng.json).

Times the OL application netlist — 96 stream PIs feeding 160 gates, the
most PI-heavy circuit in the reproduction — over the paper's full workload:
a 64x64 probability grid (Section 5.3.2), i.e. a 256-tile batch through the
16-pixel netlist, so stream generation (not logic) dominates end-to-end cost
exactly as Khatamifard et al. report for SC memory systems.  Two key
disciplines:

  * **legacy** — one PRNG split and one ``bitstream.generate`` dispatch per
    PI inside the jit, each materializing an unpacked ``(W, 32)`` uniform
    tensor (the pre-PR-3 behavior, kept as ``key_mode="legacy"``);
  * **batched** — ONE fused threshold+pack pass over the plan's stream table
    (``bitstream.generate_batch`` / kernels/sng.py), packing by
    compare-and-accumulate with no unpacked tensor.

Both run end-to-end through ``executor.execute_value`` (generation + gate
passes + decode in one jit), so the headline ``speedup`` is the acceptance
metric: batched must be >= 3X faster end-to-end at BL=1024.  A gen-only
microbench isolates the stream-generation phase itself.

Output schema:
  {"bitstream_length", "netlist", "n_stream_pis", "batch", "legacy_ms",
   "batched_ms", "speedup", "gen_only": {"legacy_ms", "batched_ms",
   "speedup"}}
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core import apps, executor
from repro.core.appnet import APP_NETLISTS
from repro.core.plan import compile_plan

from .common import time_ms as _time


def run(verbose: bool = True, smoke: bool = False) -> dict:
    # Smoke keeps enough work (batch x BL) for the gen-vs-pass balance to
    # resemble the full run, so the CI perf diff against the committed
    # record stays meaningful.
    bl = 512 if smoke else 1024
    iters = 3 if smoke else 20
    batch = 64 if smoke else 256          # full run: the 64x64 grid, 16 px/tile
    net = APP_NETLISTS["ol"]()
    rng = np.random.default_rng(0)
    # appnet_inputs returns host-f32 leaves (cheap splat, serving-friendly);
    # this loop re-dispatches the SAME values, so pin them on device once —
    # otherwise every timed call pays 96 host->device transfers that dwarf
    # the generation phase being measured.
    values = {k: jax.numpy.asarray(v) for k, v in
              apps.appnet_inputs("ol", p=rng.uniform(0.5, 1.0, (batch, 16, 6))).items()}
    key = jax.random.key(0)
    n_pis = compile_plan(net).stream_table.n_rows   # stream PIs only

    end_to_end = {}
    for mode in ("legacy", "batched"):
        end_to_end[mode] = _time(
            lambda m=mode: executor.execute_value(net, values, key, bl,
                                                  key_mode=m), iters)

    # Gen-only phase: the same per-PI loop vs one stream-table pass, jitted
    # standalone so the logic passes don't dilute the comparison.
    gen_only = {}
    for mode in ("legacy", "batched"):
        fn = jax.jit(lambda k, m=mode: executor._gen_pi_streams(
            tuple(net.pis), values, k, bl, key_mode=m))
        gen_only[mode] = _time(lambda: fn(key), iters)

    results = {
        "bitstream_length": bl,
        "netlist": net.name,
        "n_stream_pis": n_pis,
        "batch": batch,
        "legacy_ms": round(end_to_end["legacy"], 3),
        "batched_ms": round(end_to_end["batched"], 3),
        "speedup": round(end_to_end["legacy"] / end_to_end["batched"], 2),
        "gen_only": {
            "legacy_ms": round(gen_only["legacy"], 3),
            "batched_ms": round(gen_only["batched"], 3),
            "speedup": round(gen_only["legacy"] / gen_only["batched"], 2),
        },
        # Phase breakdown (Table-8 style): the gen-only microbench is the
        # stream-generation phase of the batched end-to-end run; the rest
        # is logic passes + decode.
        "phases": {
            "gen_ms": round(gen_only["batched"], 3),
            "pass_ms": round(max(end_to_end["batched"]
                                 - gen_only["batched"], 0.0), 3),
            "total_ms": round(end_to_end["batched"], 3),
        },
    }
    if verbose:
        print(f"\n== SNG bench: batched vs per-PI generation "
              f"({net.name}, {n_pis} streams, batch={batch}, BL={bl}) ==")
        print(f"  end-to-end  legacy : {end_to_end['legacy']:8.3f} ms "
              f"({n_pis} generate dispatches in-trace)")
        print(f"  end-to-end  batched: {end_to_end['batched']:8.3f} ms "
              f"(1 fused stream-table pass)")
        print(f"  speedup: {results['speedup']:.1f}X  (target: >= 3X)")
        print(f"  gen-only    legacy : {gen_only['legacy']:8.3f} ms   "
              f"batched: {gen_only['batched']:8.3f} ms  "
              f"({results['gen_only']['speedup']:.1f}X)")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    import json

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="tiny BL/iters: CI-sized sanity pass")
    parser.add_argument("--out", default=None,
                        help="output path (default BENCH_sng.json; "
                             "smoke writes BENCH_sng_smoke.json)")
    args = parser.parse_args()
    out = args.out or ("BENCH_sng_smoke.json" if args.smoke
                       else "BENCH_sng.json")
    res = run(smoke=args.smoke)
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"wrote {out}")
