"""Interpreter-vs-compiled netlist execution benchmark (BENCH_plan_exec.json).

Times ``executor.execute_value`` on the Table-2 arithmetic netlists under the
gate-by-gate reference interpreter and under the compiled execution plan
(core/plan.py + kernels/netlist_exec.py), at the paper-scale BL=1024.  The
compiled path runs stream generation, all fused gate-level passes, the
sequential word-scan (scaled division) and the StoB decode as ONE XLA
program; the interpreter pays one dispatch per gate (and eagerly unpacks
sequential circuits to time-major bits).

Also times two composed application netlists (appnet.py) where level
batching matters most — hundreds of gates collapse to a few dozen fused
passes.  The tracked headline is the geomean speedup over the Table-2 ops
(acceptance: >= 5X); appnet rows are reported separately.

Output schema (written by benchmarks/run.py to BENCH_plan_exec.json):
  {"bitstream_length": ..., "ops": [{"op", "gates", "passes", "fused_mux",
   "interpreter_ms", "compiled_ms", "speedup"}, ...],
   "geomean_speedup_table2": ..., "appnets": [...]}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import circuits, executor
from repro.core.appnet import APP_NETLISTS
from repro.core.plan import compile_plan

from .common import fmt_table, geomean, time_ms

TABLE2_OPS = (
    ("scaled_add", circuits.sc_scaled_add, {"a": 0.3, "b": 0.7}),
    ("multiply", circuits.sc_multiply, {"a": 0.6, "b": 0.5}),
    ("abs_sub", circuits.sc_abs_sub, {"a": 0.8, "b": 0.3}),
    ("scaled_div", circuits.sc_scaled_div, {"a": 0.3, "b": 0.5}),
    ("sqrt", circuits.sc_sqrt, {"a": 0.5}),
    ("exp", circuits.sc_exp, {"a": 0.5}),
)


def _time_backend(net, values, key, bl, backend, iters) -> float:
    """Min-of-iters wall time (ms) for one execute_value call (shared
    measurement protocol — see benchmarks/common.py time_ms)."""
    fn = lambda: executor.execute_value(net, values, key, bl, backend=backend)
    return time_ms(fn, iters)


def _bench_net(name, net, values, key, bl, iters) -> dict:
    plan = compile_plan(net)
    interp = _time_backend(net, values, key, bl, "reference", iters)
    comp = _time_backend(net, values, key, bl, "compiled", iters)
    return {
        "op": name, "gates": plan.n_gates, "passes": plan.n_passes,
        "fused_mux": plan.n_fused_mux,
        "interpreter_ms": round(interp, 4), "compiled_ms": round(comp, 4),
        "speedup": round(interp / comp, 2),
    }


def _appnet_cases(smoke: bool):
    from repro.core import apps
    ol_values = apps.appnet_inputs("ol", p=np.full((16, 6), 0.9))
    cases = [("ol_app_x16", APP_NETLISTS["ol"](), ol_values)]
    if not smoke:
        lit_values = apps.appnet_inputs("lit", a=np.linspace(0.1, 0.9, 81))
        cases.append(("lit_app", APP_NETLISTS["lit"](), lit_values))
    return cases


def run(verbose=True, smoke=False) -> dict:
    bl = 128 if smoke else 1024
    iters = 3 if smoke else 30
    key = jax.random.key(0)

    ops = []
    for name, builder, values in TABLE2_OPS:
        net = builder()
        vals = {k: jnp.float32(x) for k, x in values.items()}
        ops.append(_bench_net(name, net, vals, key, bl, iters))

    appnets = [_bench_net(name, net, vals, key, min(bl, 256), max(iters // 3, 2))
               for name, net, vals in _appnet_cases(smoke)]

    g = geomean([o["speedup"] for o in ops])

    # Phase breakdown for one representative op (Table-8 style attribution):
    # stream generation on its own jitted entry vs the full compiled run.
    pname, pbuilder, pvalues = TABLE2_OPS[1]        # multiply
    pnet = pbuilder()
    pvals = {k: jnp.float32(x) for k, x in pvalues.items()}
    gen_fn = jax.jit(lambda k: executor._gen_pi_streams(
        tuple(pnet.pis), pvals, k, bl))
    gen_ms = time_ms(lambda: gen_fn(key), iters)
    total_ms = next(o["compiled_ms"] for o in ops if o["op"] == pname)
    phases = {"op": pname, "gen_ms": round(gen_ms, 4),
              "pass_ms": round(max(total_ms - gen_ms, 0.0), 4),
              "total_ms": total_ms}

    results = {"bitstream_length": bl, "ops": ops,
               "geomean_speedup_table2": round(g, 2), "appnets": appnets,
               "phases": phases}
    if verbose:
        rows = [[o["op"], o["gates"], o["passes"], o["fused_mux"],
                 f"{o['interpreter_ms']:.3f}", f"{o['compiled_ms']:.3f}",
                 f"{o['speedup']:.1f}X"] for o in ops + appnets]
        print(fmt_table(
            ["Netlist", "Gates", "Passes", "FusedMUX", "Interp(ms)",
             "Compiled(ms)", "Speedup"],
            rows, title=f"\n== Plan-exec bench: interpreter vs compiled "
                        f"(BL={bl}) =="))
        print(f"\n  Geomean speedup over Table-2 ops: {g:.1f}X "
              f"(target: >= 5X)")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import json
    res = run()
    with open("BENCH_plan_exec.json", "w") as f:
        json.dump(res, f, indent=2)
    print("wrote BENCH_plan_exec.json")
