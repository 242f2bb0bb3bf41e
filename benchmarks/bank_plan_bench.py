"""Merged bank-plan vs looped per-netlist execution (BENCH_bank_plan.json).

Times one heterogeneous bank of 16 Table-2/Table-3 netlist instances at
BL=1024 two ways:

  * **looped** — one ``executor.execute_value`` dispatch per member, the
    pre-bank-merging serving model (each member is itself a compiled fused
    plan, so this baseline is already the PR-1 fast path);
  * **merged** — ONE ``executor.run([ExecRequest, ...])`` call: all members merge
    into a single bank plan (``core/plan.compile_bank_plan``) whose levels
    type-batch gates across members, executed as a single jit dispatch
    (sequential members share one merged scan).

Both paths are bit-identical (pinned by tests/test_bank_plan.py); the tracked
headline is the merged-over-looped wall-clock speedup (acceptance: >= 3X for
the 16-member bank).  The record also maps the pass counts onto the [n, m]
bank cycle model (``arch.evaluate_bank_plan``) for the measured bank and for
each Table-3 application's full cost-stage instance set — the architectural
view of the same memory-level-parallelism win.

The record also splits merged wall-clock into a stream-generation phase
(``gen_ms`` — the batched bulk-BtoS pass, timed via
``executor.generate_bank_streams``) and the remaining logic/decode phase
(``pass_ms = merged_ms - gen_ms``), so PR-over-PR perf work can see which
phase moved.

Each arch record also carries the Algorithm-1 *scheduled* cycle pricing
(``schedule_cycles`` / ``looped_schedule_cycles``, from the ``Schedule`` the
compiler pipeline attaches to every plan) and their ratio
``schedule_speedup`` — named with the "speedup" substring so
``check_regression.py`` auto-tracks it PR over PR.

Output schema (written here and by benchmarks/run.py):
  {"bitstream_length", "n_members", "members", "key_mode", "looped_ms",
   "merged_ms", "gen_ms", "pass_ms", "speedup", "merged_passes",
   "looped_passes", "arch_bank": {..., "schedule_cycles",
   "schedule_speedup"}, "table3_banks": {app: {...}}}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import apps, arch, circuits, executor
from repro.core.plan import compile_bank_plan

from .common import time_ms as _time


def bank_members() -> tuple[list, list, list]:
    """16 heterogeneous members: one bank serving stage-circuit instances of
    all four Table-3 applications (the circuits ``apps.*_cost_stages`` feeds
    Algorithm 1: LIT's square/mean/abs-sub/sqrt, OL's product multiplies,
    HDP's variable-select MUXes and divider, KDE's abs-sub/exp ladder) plus a
    Table-2 exp instance — the paper's Fig. 8 workload shape, many small
    circuit instances per bank."""
    members = [
        ("lit/square", circuits.sc_multiply, {"a": 0.45, "b": 0.45}),
        ("lit/mean", circuits.sc_scaled_add, {"a": 0.4, "b": 0.6}),
        ("lit/var", circuits.sc_abs_sub, {"a": 0.5, "b": 0.2}),
        ("lit/sigma", circuits.sc_sqrt, {"a": 0.3}),
        ("ol/prod0", circuits.sc_multiply, {"a": 0.9, "b": 0.9}),
        ("ol/prod1", circuits.sc_multiply, {"a": 0.81, "b": 0.9}),
        ("ol/prod2", circuits.sc_multiply, {"a": 0.73, "b": 0.81}),
        ("hdp/mux_e", circuits.sc_scaled_add_var,
         {"a": 0.5, "b": 0.5, "s": 0.5}),
        ("hdp/mux_ne", circuits.sc_scaled_add_var,
         {"a": 0.4, "b": 0.6, "s": 0.5}),
        ("hdp/num", circuits.sc_multiply, {"a": 0.5, "b": 0.5}),
        ("hdp/div", circuits.sc_scaled_div, {"a": 0.25, "b": 0.25}),
        ("kde/dist", circuits.sc_abs_sub, {"a": 0.4, "b": 0.7}),
        ("kde/exp", lambda: circuits.sc_exp(0.8), {"a": 0.3}),
        ("kde/prod", circuits.sc_multiply, {"a": 0.7, "b": 0.7}),
        ("kde/mean", circuits.sc_scaled_add, {"a": 0.5, "b": 0.3}),
        ("t2/exp", circuits.sc_exp, {"a": 0.5}),
    ]
    nets = [builder() for _, builder, _ in members]
    values = [{k: jnp.float32(v) for k, v in vals.items()}
              for _, _, vals in members]
    names = [name for name, _, _ in members]
    return nets, values, names


def _arch_record(bank, cfg) -> dict:
    c = arch.evaluate_bank_plan(bank, cfg)
    # "schedule_speedup" keeps the *speedup* substring on purpose:
    # check_regression.py auto-tracks speedup-named numeric fields.
    return {"n_members": c.n_members, "merged_passes": c.merged_passes,
            "looped_passes": c.looped_passes,
            "pipeline_factor": c.pipeline_factor,
            "merged_cycles": c.merged_cycles, "looped_cycles": c.looped_cycles,
            "simd_speedup": round(c.simd_speedup, 2),
            "schedule_cycles": c.schedule_cycles,
            "looped_schedule_cycles": c.looped_schedule_cycles,
            "schedule_speedup": round(c.schedule_speedup, 2)}


def run(verbose: bool = True, smoke: bool = False) -> dict:
    bl = 128 if smoke else 1024
    iters = 3 if smoke else 20
    nets, values, names = bank_members()
    keys = jax.random.split(jax.random.key(0), len(nets))

    merged_opts = executor.ExecOptions(bitstream_length=bl, decode=True)
    merged_fn = lambda: executor.run(
        [executor.ExecRequest(n, v, keys[i], merged_opts)
         for i, (n, v) in enumerate(zip(nets, values))])
    looped_fn = lambda: [executor.execute_value(n, v, keys[i], bl)
                         for i, (n, v) in enumerate(zip(nets, values))]
    merged_ms = _time(merged_fn, iters)
    looped_ms = _time(looped_fn, iters)

    bank = compile_bank_plan(nets)
    # Phase split: time the stream-generation phase on its own jitted entry;
    # the remainder of the merged wall-clock is logic passes + decode.
    vals_f32 = tuple({k: jnp.asarray(v, jnp.float32) for k, v in v_.items()}
                     for v_ in values)
    gen_fn = lambda: executor.generate_bank_streams(bank, vals_f32, keys, bl)
    gen_ms = _time(gen_fn, iters)
    cfg = arch.StochIMCConfig(bitstream_length=bl)
    table3 = {app: _arch_record(
        compile_bank_plan(apps.cost_stage_netlists(app)), cfg)
        for app in apps.APPS}

    results = {
        "bitstream_length": bl,
        "n_members": len(nets),
        "members": names,
        "key_mode": executor.DEFAULT_KEY_MODE,
        "looped_ms": round(looped_ms, 3),
        "merged_ms": round(merged_ms, 3),
        "gen_ms": round(gen_ms, 3),
        "pass_ms": round(max(merged_ms - gen_ms, 0.0), 3),
        "phases": {
            "gen_ms": round(gen_ms, 3),
            "pass_ms": round(max(merged_ms - gen_ms, 0.0), 3),
            "total_ms": round(merged_ms, 3),
        },
        "speedup": round(looped_ms / merged_ms, 2),
        "merged_passes": bank.n_passes,
        "looped_passes": bank.n_passes_looped,
        "arch_bank": _arch_record(bank, cfg),
        "table3_banks": table3,
    }
    if verbose:
        print(f"\n== Bank-plan bench: merged vs looped "
              f"({len(nets)} members, BL={bl}) ==")
        print(f"  looped : {looped_ms:8.3f} ms  "
              f"({bank.n_passes_looped} passes + {len(nets)} dispatches)")
        print(f"  merged : {merged_ms:8.3f} ms  "
              f"({bank.n_passes} passes, 1 dispatch; "
              f"gen {results['gen_ms']:.3f} ms + "
              f"pass {results['pass_ms']:.3f} ms)")
        print(f"  speedup: {results['speedup']:.1f}X  (target: >= 3X)")
        print("  [n, m] bank model — Table-3 apps as full cost-stage banks:")
        for app, r in table3.items():
            print(f"    {app.upper():4s} {r['n_members']:4d} members  "
                  f"passes {r['looped_passes']:5d} -> {r['merged_passes']:4d}  "
                  f"cycles {r['looped_cycles']:6d} -> {r['merged_cycles']:5d}  "
                  f"({r['simd_speedup']:.1f}X)")
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
                        help="output path (default BENCH_bank_plan.json; "
                             "smoke writes BENCH_bank_plan_smoke.json)")
    args = parser.parse_args()
    out = args.out or ("BENCH_bank_plan_smoke.json" if args.smoke
                       else "BENCH_bank_plan.json")
    res = run(smoke=args.smoke)
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"wrote {out}")
