"""Fig. 10 — energy breakdown (logic / preset / input-init / peripheral) per
application for binary IMC, [22], and Stoch-IMC.
"""
from __future__ import annotations

from repro.core import apps

from . import table3_apps
from .common import fmt_table


def run(verbose=True) -> dict:
    t3 = table3_apps.run(verbose=False)
    results = {}
    rows = []
    for app in apps.APPS:
        bd = t3["apps"][app]["energy_breakdown"]
        res = {}
        for method, e in (("binary", bd["binary"]), ("[22]", bd["cram"]),
                          ("stoch-imc", bd["stoch"])):
            res[method] = e.shares()
            rows.append([app.upper(), method] +
                        [f"{100 * res[method][k]:.1f}%" for k in
                         ("logic", "preset", "input_init", "peripheral")])
        results[app] = res
    if verbose:
        print(fmt_table(["App", "Method", "logic", "preset(reset)",
                         "input-init", "peripheral"], rows,
                        title="\n== Fig. 10: energy breakdown =="))
        print("\n  Paper (qualitative): logic+reset dominate everywhere; "
              "stochastic methods shift share from logic to reset; Stoch-IMC "
              "peripheral > [22] (accumulators + BtoS).")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
